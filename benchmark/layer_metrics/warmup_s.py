"""Child start to its first finished step (training) or first streamed
token (serving): boot of the model, weights, compile or cache load."""


def read(run):
    return run.get("warmup_s")
