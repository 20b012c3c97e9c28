#!/bin/sh
# The CI shape of the soak gauntlet: one preemption + one flap + one
# resize against the durable elastic run, training legs only (no serve
# trace, no replan leg); scripts/soak.sh is the full gauntlet. Exit code =
# failed gates.
set -e
cd "$(dirname "$0")/.."
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
JAX_PLATFORMS=cpu \
exec python scripts/soak.py --smoke "$@"
