"""`idle_share.decode`'s reading (1 - union of device-op intervals /
traced window, averaged over the chips), in the cells that report
ttft_p95_ms."""
import harness

read = harness.reader("idle_share.decode")
