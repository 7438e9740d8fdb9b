"""EWQ plan + quantize: host seconds from the start of the program's plan
(``plan_for_variant``) to quantized weights on the device
(``model.compile_plan``, ended by ``block_until_ready``)."""

LAYER = "EWQ plan + quantize"
UNIT, BETTER, MOVES = "s", "lower", "setup_s"


def read(rec):
    return rec["plan_s"]
