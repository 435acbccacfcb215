"""Device time of the decode step's ops in the program's `attention` scope,
in milliseconds per run of the step on the first chip: the synchronous
ops inside each `step` run, named by `ContinuousBatcher.op_scopes()`
(`scopes.py`).  Nothing to read where the program names no scopes."""
import scopes

read = scopes.reader("step", "attention")
