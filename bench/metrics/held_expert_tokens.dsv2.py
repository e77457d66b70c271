"""Tokens each held expert computes per model step: the (token, expert)
pairs the window routed to the held experts, as the program counts them
(the engine's ``routed_held``), over the held experts times the expert
layers times the model steps.  Nothing to read from a program that does
not count them."""


def read(run):
    c = run.counters
    held = c.get("routed_held")
    if held is None or not c.get("model_steps"):
        return None
    return held / (c["held_experts"] * c["moe_layers"] * c["model_steps"])
