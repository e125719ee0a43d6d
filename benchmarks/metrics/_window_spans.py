"""Shared by the readers of per-generation times: the window's
generations as (period, train span seconds), with the generation in
whose period the profiler stops and writes its trace left out where
the window holds another (a traced run only; launch L's generation is
window generation L - 1)."""


def generations(run):
    w = run.window
    if not w.closed:
        return []
    train = {int(s["launch"]): s for s in run.spans if s.get("span") == "train" and "launch" in s}
    rows = []
    for k, period in enumerate(w.periods(), start=1):
        sp = train.get(k + 1)
        rows.append({"generation": k, "period_s": period, "train_s": sp["dur_s"] if sp else None})
    # launch L is window generation L - 1, so the generation after it is L
    if run.traced_launch is not None and len(rows) > 1:
        rows = [r for r in rows if r["generation"] != run.traced_launch]
    return rows
