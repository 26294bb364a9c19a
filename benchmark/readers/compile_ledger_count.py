"""Compile episodes the program's compile ledger charged to the steady
phase inside the window (there should be none)."""


def read(ctx):
    return float(len(ctx["compiles"]))
