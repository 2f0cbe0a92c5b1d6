"""Share of its roofline, in percent, that the CSR extend kernel
(``csr_extend`` / ``csr_extend_bucketed``) reaches in the traced window:
the chip's least time for the work its calls require
(``roofline.csr_extend_work`` from each call's shapes and the target's,
bandwidth-bound) over the kernel's device time."""

from sgebench import roofline, xplane


def read(run):
    calls = xplane.kernel_calls(run.trace, "csr_extend")
    ks = run.kernel_shapes
    least = busy = 0.0
    for c in calls:
        b = xplane.extend_shape(c)
        work = roofline.csr_extend_work(b, ks["w"], xplane.extend_parents(c, b),
                                        ks["nnz_plane"], ks["n_t"])
        least += roofline.least_seconds(work, run.device_kind)
        busy += c.dur_ns / 1e9
    return 100.0 * least / busy if busy > 0 else None
