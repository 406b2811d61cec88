"""The work causal flash attention needed in the traced training steps:
every row a device stepped through, every layer, forward and backward.
Reads ``shapes`` and ``train`` (``rows_per_device_step``, ``traced_steps``,
``seq_len``) of a ``train_job`` run's observations."""
from benchmarks import flops


def work(obs):
    t = obs["train"]
    return flops.flash_train_work(
        obs["shapes"], rows=t["rows_per_device_step"] * t["traced_steps"],
        seq_len=t["seq_len"])
