"""Share of its roofline, in percent, that the CSR arc-consistency sweep
kernel (``csr_arc_sweep``, run by ``prepare`` on a CSR index) reaches in
the traced window: the chip's least time for the work its calls require
(``roofline.csr_arc_sweep_work`` from each call's shapes and the
target's, bandwidth-bound) over the kernel's device time."""

from sgebench import roofline, xplane


def read(run):
    calls = xplane.kernel_calls(run.trace, "csr_arc_sweep")
    ks = run.kernel_shapes
    least = busy = 0.0
    for c in calls:
        n_arcs = xplane.sweep_arcs(c, ks["n_t"])
        work = roofline.csr_arc_sweep_work(n_arcs, ks["n_planes"], ks["n_t"],
                                           ks["w"], ks["nnz_plane"])
        least += roofline.least_seconds(work, run.device_kind)
        busy += c.dur_ns / 1e9
    return 100.0 * least / busy if busy > 0 else None
