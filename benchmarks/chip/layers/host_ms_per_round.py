"""Per round, the time inside the benchmark's ``run_round`` span in which
the device was idle: the host's control plane (scheduling, presampling,
staging, the stats readback) as the round pays it."""


def read(run):
    span_s, busy_s = run.trace.busy_in("run_round")
    n = len([s for s in run.trace.spans if s[0] == "run_round"
             and s[1] >= run.trace.window[0] and s[2] <= run.trace.window[1]])
    return None if n == 0 else (span_s - busy_s) / n * 1e3
