"""Tokens the engine emitted per step and slot over the window, from
``LLMServer.stats()`` before and after it."""


def read(ctx):
    d = {k: ctx["stats1"][k] - ctx["stats0"][k]
         for k in ("tokens_out", "steps")}
    if d["steps"] <= 0:
        return None
    return 100.0 * d["tokens_out"] / (d["steps"] * ctx["stats1"]["num_slots"])
