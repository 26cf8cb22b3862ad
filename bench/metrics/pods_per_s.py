"""Pods placed in the window's cycles over the window's wall seconds."""

from bench import readers


def read(run):
    return readers.pods_per_s(run)
