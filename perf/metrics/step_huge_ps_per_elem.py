"""Picoseconds a streamed element over the once-a-row steps whose larger
operand holds 2^26 elements or more (the size class of a slice at one
chip's own memory target, which no 2^25-target plan reaches): their
device op seconds in the traced window over (slices a chip completed x
elements they stream a slice: operand in + result out). One 8-byte read
and one write at the v5e's 819 GB/s are 19.5 ps. Nothing without a
trace, without the program's op table, from a table whose steps do not
say their larger operand's size, or where the plan has no such step."""

from perf import step_lib

name = 'step_huge_ps_per_elem'
unit = 'ps/elem'
layer = 'kernels'
moves = 'amplitude_s'
workloads = ['sycamore53_m20_share.amp_slices']

HUGE = 1 << 26


def read(run):
    joined, units = step_lib.join(run), step_lib.units_per_chip(run)
    if not joined or not units:
        return None
    rows = [row for row in step_lib.stem_rows(joined) if row.get("operand", 0) >= HUGE]
    elements = sum(row["elements"] for row in rows)
    if not elements:
        return None
    return 1e12 * sum(map(step_lib.step_total, rows)) / (units * elements)
