"""The part of `allreduce_ms.decode` during which no op outside the
`tp_all_reduce` scope runs on the first chip: the all-reduce time that
nothing hides, in milliseconds per run of the decode step
(`collectives.py`)."""
import collectives

read = collectives.reader("step", "tp_all_reduce", exposed=True)
