"""Model step: seconds of Python tracing and lowering to MLIR, over every
program family, that the program's compile listener recorded before the
window's start. Moves ``setup_s``."""
from bench.lib import records


def read(ctx):
    return records.compile_seconds(ctx, records.TRACE_PHASES)
