"""The one jitted program that makes parameters, optimizer state and the batch pool from the seed, on the mesh, blocked."""


def read(ctx):
    return ctx["phases"].get("state_s")
