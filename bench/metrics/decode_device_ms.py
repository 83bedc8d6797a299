"""Model step: device milliseconds of the programs launched inside
``engine.decode`` spans (the jitted decode step alone, without the reset
or the argmax; ``bench/engine_trace.py``) over the number of those spans in
the traced window. Reads the engine-span reduction that
``bench/trace_cell.py`` attaches to the run as ``run.engine_trace``."""


def read(run):
    t = getattr(run, "engine_trace", None)
    return None if t is None else t.device_ms("engine.decode")
