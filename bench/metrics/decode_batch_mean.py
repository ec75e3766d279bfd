"""Mean number of slots each batched decode step of the window carried
(the engine's own decode event, one per step)."""


def read(view):
    sizes = [len(s["positions"]) for s in view.observed.get("steps", [])
             if s["positions"]]
    return sum(sizes) / len(sizes) if sizes else None
