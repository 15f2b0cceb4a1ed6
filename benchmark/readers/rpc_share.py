"""Share of the verbs' time one volume-server RPC took: the summed
durations of the program's spans of that RPC (name "Service/Method"),
over the verb trace ids of the window, against the verbs' summed wall
time.  RPCs of one verb run one after another, so the share is at most
100%."""


def read(w, trace, devices, span: str):
    total = sum(v["seconds"] for v in w.verbs)
    if not total:
        return None
    tids = {v["tid"] for v in w.verbs}
    spent = sum(s["duration_ms"] for s in w.spans
                if s["name"] == span and s["trace_id"] in tids) / 1e3
    return 100.0 * spent / total
