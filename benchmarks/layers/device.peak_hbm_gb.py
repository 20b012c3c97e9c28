"""``compiled.memory_analysis()`` of the step: arguments + outputs +
temporaries - aliased, per device. The runtime's ``peak_bytes_in_use``
leaves a program's temporaries out on this runtime (PERF.md)."""

NAME, UNIT = "device.peak_hbm_gb", "GB"
LAYER, MOVES = "Device", "tokens_per_s_per_chip"


def read(run):
    total = run.memory.get("total")
    return total / 1e9 if total else None
