"""Program: the whole served stream's share of the chip's peak — output
tokens per second times the operations one token requires (2 per matmul
parameter incl. the tied head, attention over the window's mean live
context) over chips x peak."""


def read(facts):
    rate = facts["end_to_end"].get("decode_tok_per_s")
    if not rate:
        return None
    flops = facts["reference"].flops_per_token(facts["config"],
                                               facts["mean_context"])
    return 100.0 * rate * flops / (facts["chips"] * facts["peaks"]["flops"])
