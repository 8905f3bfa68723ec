"""Grid-program device time per task-tick, from the profiler trace (ns)."""
from bench.harness.readers import tick_ns_per_task_tick


def read(run):
    return tick_ns_per_task_tick(run)
