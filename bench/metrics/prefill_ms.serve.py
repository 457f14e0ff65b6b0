"""Device time per prefill call: the mean duration of the program
``jit_prefill`` on the chip in the traced stretch."""


def read(run):
    times = run.trace.module_times("jit_prefill") if run.trace else []
    return sum(times) / len(times) * 1e3 if times else None
