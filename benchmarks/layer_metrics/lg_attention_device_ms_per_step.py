"""Device time under the ``L*.attention`` scopes of a net whose attention is
local on some layers and global on others, forward, recomputed and backward,
per train step: ``attention_device_ms_per_step``'s reading, under the name
this net's cells list."""
from benchmarks.layer_metrics import attention_device_ms_per_step

read = attention_device_ms_per_step.read
