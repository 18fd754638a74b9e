"""1 - device busy / traced window, from the profiler trace."""

import trace_reduce


def read(obs, trace, cell):
    return trace_reduce.idle_share(trace, obs.get("window_s"))
