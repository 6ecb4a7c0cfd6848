"""Test metric: how many jobs the run measured."""


def read(run):
    return float(len(run.jobs))
