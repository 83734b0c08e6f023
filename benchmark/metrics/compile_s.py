"""make_train_step, its lowering and step.lower(...).compile(): from the persistent cache after a cell's first run."""


def read(ctx):
    return ctx["phases"].get("compile_s")
