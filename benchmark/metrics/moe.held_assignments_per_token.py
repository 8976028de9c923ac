"""(token, expert) pairs that fell to the experts held on this chip, a token
a routed-expert layer: ``serving.moe_assignments{kind=held}`` over the tokens
that went through such a layer (``{kind=routed}`` over the configuration's
``num_experts_per_tok``), whole process. Uniform routing over the share reads
``num_experts_per_tok * n_routed_experts / router_width`` (0.375 at 6 x 10 /
160). Beside it ``load_max_over_mean``: the busiest held expert's pairs over
the mean of them (``serving.moe_expert_load{expert=...}``), 1.0 when the load
is even. A program that counts no such pairs has nothing to read: None."""
from benchmark.lib import program_spans as PS


def read(ctx):
    held = PS.counter("serving.moe_assignments", kind="held")
    routed = PS.counter("serving.moe_assignments", kind="routed")
    top_k = ctx.cfg.get("num_experts_per_tok")
    if held is None or not routed or not top_k:
        return None
    out = {"value": held * top_k / routed, "held": held, "routed": routed}
    first = ctx.cfg.get("experts_held_first", 0)
    loads = [PS.counter("serving.moe_expert_load", expert=e)
             for e in range(first, first + ctx.cfg.get("n_routed_experts", 0))]
    loads = [x for x in loads if x is not None]
    if loads and sum(loads) > 0:
        out["load_max_over_mean"] = max(loads) * len(loads) / sum(loads)
    return out
