"""Scheduler: tokens emitted in the window over (window steps x slots),
in percent. Counted from the served requests (``ServeResult``)."""


def read(run):
    if run.window.steps <= 0:
        return None
    return 100.0 * run.window.tokens / (run.window.steps * run.slots)
