"""Model step: seconds of backend compile or persistent-cache load, over
every program family, that the program's compile listener recorded before
the window's start. Moves ``setup_s``."""
from bench.lib import records


def read(ctx):
    return records.compile_seconds(ctx, records.COMPILE_PHASES)
