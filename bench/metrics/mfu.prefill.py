"""`mfu.decode`'s reading (model FLOPs of every token the traced window
processed over window seconds x chips x peak bf16 FLOP/s), in the cells
that report ttft_p95_ms."""
import harness

read = harness.reader("mfu.decode")
