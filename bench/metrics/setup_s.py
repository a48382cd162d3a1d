"""Set-up seconds: process start to a warm engine (model, weights made on
the chip, engine, warm-up waves)."""


def read(run):
    return run.setup_s
