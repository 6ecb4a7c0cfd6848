"""Seconds from the harness's start to the window's: imports, finding the
chip, making the key sets, and the warm-up job with its compiles or
compile-cache loads."""


def read(run):
    return run.setup_s
