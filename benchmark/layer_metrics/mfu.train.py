"""Program: the whole step's share of the chips' peak — images per
second times the operations one image requires forward and backward
(shape count, 2 per MAC, nothing recomputed) over chips x peak."""


def read(facts):
    rate = facts["end_to_end"].get("train_img_per_s")
    if not rate:
        return None
    flops = facts["reference"].train_flops_per_image(facts["config"])
    return 100.0 * rate * flops / (facts["chips"] * facts["peaks"]["flops"])
