"""Share of the traced window in which the chip that holds the fan-in's
survivor was idle while the host was in the fan-in: dispatching a level
(``tnc.partitioned.fanin_level``) or, since dispatch is asynchronous,
waiting for the survivor (``tnc.partitioned.fetch``). That chip is idle
there only while a partner's tensor is still being contracted or moved
chip to chip: what the pair contractions wait for. Chip by chip from the
trace (``perf/chip_lib.py``). Nothing without a trace, or from a program
that names no pair program or writes neither span."""

from perf import chip_lib

name = 'fanin_move_wait_pct'
unit = '%'
layer = 'multi-chip'
moves = 'amplitude_s'
workloads = ['sycamore30_m14_part4.fanin_calls']


def read(run):
    chip = chip_lib.survivor(run.window.get("per_chip"))
    return chip_lib.idle_pct(chip, "partitioned.fanin_level", "partitioned.fetch")
