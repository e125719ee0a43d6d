"""Process start to the start of the first `train` span."""


def read(run):
    first = [s for s in run.spans if s.get("span") == "train" and s.get("launch") == 1]
    if not first:
        return None
    return first[0]["start"] - run.t0
