"""executor_ms.<cell kind>: per request, the summed host time of the
harness's stage-executor spans (``run_range`` ended by
``block_until_ready``), averaged over the requests the profiler did not
slow. Independent of how many stages the plan cuts."""


def read(run):
    keep = {id(u) for u in run.host_units()}
    units = {i for i, u in enumerate(run.units) if id(u) in keep}
    per = {}
    for name, t0, t1, unit in run.spans:
        if name == "bench.executor" and unit in units:
            per[unit] = per.get(unit, 0.0) + (t1 - t0)
    return sum(per.values()) / len(per) * 1e3 if per else None
