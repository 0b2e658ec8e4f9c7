"""Share of the held experts' computed rows that no token was routed
to, over the window: 100 x (expert rows computed - rows routed) / rows
computed, from the program's MoE counters: a decode step computes a
buffer of 128 rows per held expert (a further round of such buffers
where one expert is routed more), and counts every slot, idle ones
included."""


def read(rec):
    c = rec["counts"]
    if not c.get("moe_rows_computed"):
        return None
    return 100.0 * (c["moe_rows_computed"] - c["moe_rows_routed"]) \
        / c["moe_rows_computed"]
