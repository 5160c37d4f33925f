"""Seconds of the program's ``train.plan`` span in set-up: the link
matrix read in and the Pipette search for the cell's chips."""


def read(run):
    return run["record"].get("plan_s")
