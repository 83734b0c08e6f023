"""Tokens of the steps completed in the window / seconds between the first
and the last completion / chips."""


def read(ctx):
    if ctx["job"]["unit"] != "tokens":
        return None
    return ctx["units_per_s_per_chip"]
