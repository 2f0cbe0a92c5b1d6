"""Mean host seconds of ``Enumerator.prepare`` (domains, ordering, plan)
over the queries prepared inside the window."""


def read(run):
    t = [r.t_prepared - r.t_start for r in run.records
         if r.t_prepared and r.t_start < run.t1]
    return sum(t) / len(t) if t else None
