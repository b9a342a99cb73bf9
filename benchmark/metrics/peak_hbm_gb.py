"""``peak_bytes_in_use`` of the fullest device when the window closes,
set-up included: the structure build's buffers are part of what must fit."""


def read(run):
    return run.device["memory_peak_bytes"] / 1e9
