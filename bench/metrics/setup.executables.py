"""Executables the compiled-evaluator store built during set-up (its
misses): each is traced and lowered, then compiled or loaded from the
persistent compilation cache."""


def read(run):
    return float(run.setup["executables"])
