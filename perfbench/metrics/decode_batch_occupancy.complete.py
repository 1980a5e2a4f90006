"""Decode slots in use over the slots there are, mean over the engine
steps of the window that decoded."""
from perfbench.lib import engine_readers


def read(ctx):
    return engine_readers.batch_occupancy(ctx)
