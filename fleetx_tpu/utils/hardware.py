"""Chip peak-FLOPs table for MFU reporting.

The reference logs only tokens/s (``language_module.py:58-67``); MFU
(model FLOPs / step time / chip peak) is the TPU-native utilization metric.
bf16 dense peak per chip, public figures; ``benchmarks/peaks.json`` holds
the same figures with their sources and a test keeps the two equal.
"""

from __future__ import annotations

# substring of device_kind (lowercased) → bf16 peak FLOP/s
PEAK_FLOPS = (
    ("v6", 918e12),   # Trillium
    ("v5p", 459e12),
    ("v5", 197e12),   # v5e / "v5 lite"
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
)


def clean_cpu_env(repo_root: str, n_devices: int | None = None) -> dict:
    """os.environ copy asking for the CPU backend by name, with the checkout
    on ``PYTHONPATH`` and optionally ``n_devices`` virtual host devices.
    Shared by __graft_entry__.py and the loss-curve test."""
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [repo_root] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                       if p])
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_PLATFORM_NAME", None)
    if n_devices is not None:
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={n_devices}")
        env["XLA_FLAGS"] = " ".join(flags)
    return env


def peak_flops(device) -> float | None:
    """bf16 peak for a jax device; None off the TPU (the CPU has no MFU).
    A TPU the table does not know raises: a missing peak would make the
    MFU key vanish without a word."""
    kind = device.device_kind.lower()
    for key, peak in PEAK_FLOPS:
        if key in kind:
            return peak
    if device.platform == "tpu":
        raise ValueError(f"no peak FLOP/s on record for TPU device_kind "
                         f"{device.device_kind!r}: add it to PEAK_FLOPS")
    return None


def gpt_flops_per_token(num_layers: int, hidden_size: int, seq_len: int,
                        num_params: int | None = None,
                        vocab_size: int | None = None) -> float:
    """PaLM-style fwd+bwd FLOPs per trained token: ``6N + 12·L·H·S``.

    ``num_params`` may be passed directly (preferred); otherwise it is
    approximated from the architecture (reference model-size formula,
    ``language_module.py:102-105``).
    """
    if num_params is None:
        num_params = int(num_layers * 12 * hidden_size * hidden_size
                         + (vocab_size or 0) * hidden_size)
    return 6.0 * num_params + 12.0 * num_layers * hidden_size * seq_len
