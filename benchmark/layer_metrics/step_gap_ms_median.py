"""Median idle gap on the device between one step program's end and the
next one's start, over the traced window."""

from benchmark import trace_reduce


def read(trace, counters, cell):
    return trace_reduce.median(trace["step_gaps_ms"])
