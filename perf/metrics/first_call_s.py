"""Host clock around the warm-up calls, ending in ``block_until_ready`` (or
in the replies of the warm-up requests): program build, compile or cache
load, first execution."""

name = 'first_call_s'
unit = 's'
layer = 'program build and compile'
moves = 'setup_s'
workloads = None  # every cell that reports `moves`


def read(run):
    return run.setup.get('first_call_s')
