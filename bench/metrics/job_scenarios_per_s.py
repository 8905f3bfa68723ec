"""Job-scenarios landed in the window per second of it (job-scen/s)."""
from bench.harness.readers import job_scenarios_per_s


def read(run):
    return job_scenarios_per_s(run)
