"""Prefix cache: share of the prompt tokens sent that the prefix cache
served, so that their prefill never ran."""


def read(run):
    total = sum(len(r.prompt) for r in run.requests)
    saved = run.result["prefill_tokens_saved"]
    if not total or not saved:
        return None
    return 100.0 * saved / total
