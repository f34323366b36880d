"""Serve loop: share of the decode batch's slots that carried a request,
over all decode steps of the window (decode tokens / (steps x slots))."""


def read(run):
    r = run.result
    if not r["steps"]:
        return None
    return 100.0 * r["decode_tokens"] / (r["steps"] * r["batch_slots"])
