"""Device time of the decode step's all-reduces between chips, in
milliseconds per run of the step on the first chip: the union of the
intervals of the ops, synchronous or asynchronous, that the program puts
in its `tp_all_reduce` scope (`collectives.py`).  Nothing to read where
the program names no scopes or the step holds no such op (tp = 1)."""
import collectives

read = collectives.reader("step", "tp_all_reduce", exposed=False)
