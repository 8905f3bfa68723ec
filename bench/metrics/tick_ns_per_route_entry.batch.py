"""Grid-program device time per routed destination entry, from the
profiler trace and SweepChunk.route_entries, the sweep.device span's
count (ns): the trace's grid-program time, summed over chips, over the
program's executions times the entries one execution routes. A program
whose chunks carry no route_entries leaves the metric out."""


def read(run):
    tr = run.trace
    chunks = run.chunks_in_window()
    if tr is None or not tr.grid_runs or not chunks:
        return None
    entries = getattr(chunks[0], "route_entries", None)
    if not entries:
        return None
    return 1e9 * tr.grid_busy_s / (tr.grid_runs * entries)
