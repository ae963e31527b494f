"""Seconds the stitching compiler spent planning the cell's programs
(sum of ``StitchReport.plan_time_s``) during set-up."""


def read(run):
    return run.plan_s
