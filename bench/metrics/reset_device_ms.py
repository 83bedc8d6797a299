"""Model step, masked slot reset: device milliseconds of the programs
launched inside ``engine.reset`` spans (``bench/engine_trace.py``) over the
number of those spans in the traced window: the reset's device cost per
admitting step. Reads the engine-span reduction that ``bench/trace_cell.py``
attaches to the run as ``run.engine_trace``."""


def read(run):
    t = getattr(run, "engine_trace", None)
    return None if t is None else t.device_ms("engine.reset")
