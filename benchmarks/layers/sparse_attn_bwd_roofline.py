"""Device trace: the sparse-attention backward's share of its roofline, the
dq and the dk/dv kernels together. Least time for one backward over the
SELECTED pairs only (benchmarks/lib/kernels_sparse.py) over the mean
measured time of one ``hvd_sparse_attn_bwd_dq`` event plus one
``hvd_sparse_attn_bwd_dkv`` event on the first device."""

from benchmarks.lib import kernels, kernels_sparse, manifest as mf, scopes

NAME, UNIT = "sparse_attn_bwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
KERNELS = ("hvd_sparse_attn_bwd_dq", "hvd_sparse_attn_bwd_dkv")


def read(run):
    shape = dict(run.kernel_shapes.get("sparse_attention") or {})
    scoped = scopes.of(run)
    if scoped is None or run.peak is None or not shape:
        return None
    kernel_seconds = mf.load_module(
        "layers", "sparse_attn_fwd_roofline").kernel_seconds
    parts = [kernel_seconds(scoped, k) for k in KERNELS]
    if not all(parts):
        return None
    least, bound = kernels.roofline(
        *kernels_sparse.sparse_attn_bwd_cost(**shape), run.peak)
    mean = sum(sum(p) / len(p) for p in parts)
    run.note(f"{NAME}: {[len(p) for p in parts]} calls, mean dq + dkv "
             f"{mean * 1e6:.1f} us, least {least * 1e6:.1f} us over the "
             f"selected pairs, bound by {bound}")
    return 100.0 * least / mean
