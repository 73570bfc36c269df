"""Host milliseconds per decode step: ``greedy_decode``'s synced
``decode_s`` summed over the window's queries, over their decode steps."""


def read(r):
    q = r.raw.get("queries")
    if not q:
        return None
    return 1e3 * sum(r.raw["decode_s"]) / (q * (r.raw["new_tokens"] - 1))
