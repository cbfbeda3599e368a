"""Program: device time of the decode-step program per execution."""


def read(facts):
    prog = facts["trace"]["programs"].get(
        facts["config"]["programs"]["decode_step"])
    if not prog or not prog["n"]:
        return None
    return prog["median_s"] * 1e3
