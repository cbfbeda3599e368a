"""Program: device time of the chunk-prefill program per execution."""


def read(facts):
    prog = facts["trace"]["programs"].get(
        facts["config"]["programs"]["prefill_chunk"])
    if not prog or not prog["n"]:
        return None
    return prog["median_s"] * 1e3
