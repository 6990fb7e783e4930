"""Share of the engine's decode calls in the window that fed a prompt token
rather than producing output: the feeds are counted from the prompt lengths
the harness admitted, the output steps from the engine steps it drove."""


def read(ctx):
    c = ctx["run"].counts
    if "feed_steps" not in c or not c["engine_steps"]:
        return None
    return 100.0 * c["feed_steps"] / (c["feed_steps"] + c["engine_steps"])
