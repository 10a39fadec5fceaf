"""Requests answered inside the window, divided by the window."""


def read(run):
    return len(run.drive.completed_in_window) / run.seconds
