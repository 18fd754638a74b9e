"""Seconds of a served job outside the engine's run: the job journal's
job_submitted -> job_done, minus the engine's run_start -> run_end
(every attempt's).  Read from the window's last job."""


def read(obs, trace, cell):
    ts = {}
    engine_s, run_start = 0.0, None
    for e in obs.get("events", []):
        name = e.get("event")
        if name in ("job_submitted", "job_done"):
            ts[name] = e["ts"]
        elif name == "run_start":
            run_start = e["ts"]
        elif name == "run_end" and run_start is not None:
            engine_s += e["ts"] - run_start
            run_start = None
    if len(ts) < 2:
        return None
    return ts["job_done"] - ts["job_submitted"] - engine_s
