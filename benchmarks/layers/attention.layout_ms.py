"""Program scopes in the device trace (benchmarks/lib/scopes.py), first
device: time per step under ``hvd.flash_attention`` that is not one of the
three kernels' own events: what attention's ``[B, T, H, D]`` to ``[BH, T, D]``
packing and the transpose back cost. The kernels are told by their
``pallas_call`` ``name=`` (``hvd_flash_fwd``, ``hvd_flash_bwd_dq``,
``hvd_flash_bwd_dkv``), which lands in the op's path or HLO text."""

from benchmarks.lib import scopes

NAME, UNIT = "attention.layout_ms", "ms"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
SCOPE = "hvd.flash_attention"
KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")


def is_kernel(op) -> bool:
    return any(scopes.mentions(op, k) for k in KERNELS)


def read(run):
    scoped = scopes.of(run)
    if scoped is None:
        return None
    calls = [scoped.calls_per_step(k) for k in KERNELS]
    if not any(calls):
        return None    # kernels without a name: nothing to tell them by
    run.note(f"{NAME}: kernel events per step "
             + ", ".join(f"{k} {n:g}" for k, n in zip(KERNELS, calls)))
    return scoped.scope_ms(SCOPE, keep=lambda op: not is_kernel(op))
