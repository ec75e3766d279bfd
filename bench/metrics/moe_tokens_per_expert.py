"""Mean number of tokens routed to each held expert that a decode step's
tokens reached, per (expert layer, held expert) pair hit: the engine's
``moe_routed`` over its ``moe_experts_hit``, summed over the traced
window's decode steps.  Nothing is read where the program counts no
routing."""


def read(view):
    steps = [s for s in view.observed.get("traced_steps", [])
             if "moe_experts_hit" in s]
    hit = sum(s["moe_experts_hit"] for s in steps)
    return sum(s["moe_routed"] for s in steps) / hit if hit else None
