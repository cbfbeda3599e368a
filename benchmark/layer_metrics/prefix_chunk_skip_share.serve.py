"""Scheduler: of the prompt pages the window's placements needed, the
share the prefix trie mapped in place of a chunk dispatch: `prefix_hits`
(pages mapped from the trie) over `prefix_hits` + `prefill_chunks`,
window deltas."""


def read(facts):
    d = facts["delta"]
    pages = d.get("prefix_hits", 0) + d.get("prefill_chunks", 0)
    if not pages:
        return None
    return d["prefix_hits"] / pages
