"""The names the program gives its work inside the compiled step:
``span_audit.DEVICE_SCOPES`` in the ``op_name`` of the compiled HLO and
``hvd_*`` on every Pallas kernel of ``ops/`` (docs/observability.md, "Scopes
in the device trace"). The tiny GPT step is the benchmark's own
(tests/benchmark/bench_tiny.py), on one and on four virtual CPU devices.
"""

import contextlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

import horovod_tpu as hvd
from horovod_tpu.compile.cache import persistent_cache_disabled
from horovod_tpu.monitor import hlo_owners
from horovod_tpu.monitor.span_audit import DEVICE_SCOPES
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import layer_norm as ln
from horovod_tpu.ops import softmax_xent as sx

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark"))
import bench_tiny as tiny  # noqa: E402
import bench_tiny_afmoe as tiny_afmoe  # noqa: E402
import bench_tiny_nemotron_h as tiny_nemotron_h  # noqa: E402
import bench_tiny_sambay as tiny_sambay  # noqa: E402
import bench_tiny_sdar as tiny_sdar  # noqa: E402
import bench_tiny_smallthinker as tiny_smallthinker  # noqa: E402
import bench_tiny_sparse as tiny_sparse  # noqa: E402

from benchmarks.builders import (afmoe, gpt_decoder, nemotron_h,  # noqa: E402
                                 sambay, sdar_moe, smallthinker,
                                 sparse_moe_decoder)
from horovod_tpu.ops import selective_scan as scan  # noqa: E402
from horovod_tpu.ops import sparse_attention as spa  # noqa: E402

KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv",
           "hvd_flash_fwd_win", "hvd_flash_bwd_dq_win",
           "hvd_flash_bwd_dkv_win", "hvd_flash_fwd_bd",
           "hvd_flash_bwd_dq_bd", "hvd_flash_bwd_dkv_bd",
           "hvd_xent_fwd", "hvd_xent_bwd_dx", "hvd_xent_bwd_dw",
           "hvd_ln_fwd", "hvd_ln_bwd", "hvd_index_select",
           "hvd_sparse_attn_fwd", "hvd_sparse_attn_bwd",
           "hvd_sparse_attn_bwd_dq", "hvd_sparse_attn_bwd_dkv",
           "hvd_selective_scan_fwd", "hvd_selective_scan_bwd")
# The tiny GPT step runs the unfused LayerNorm, as every cell does today,
# and none of the sparse decoder's work: that has a tiny step of its own.
SPARSE_STEP = {"hvd.sparse_attention", "hvd.sparse_indexer", "hvd.moe_ffn"}
# Nor a window, a shared expert or a router's bias: the tiny afmoe step's
# (tests/benchmark/bench_tiny_afmoe.py).
AFMOE_STEP = {"hvd.flash_window", "hvd.shared_expert",
              "hvd.router_bias_update"}
# Nor a state-space layer, a gated memory unit or differential attention:
# the tiny sambay step's (tests/benchmark/bench_tiny_sambay.py).
SAMBAY_STEP = {"hvd.ssm", "hvd.selective_scan", "hvd.gmu",
               "hvd.diff_attention"}
# Nor the block-diffusion objective: the tiny sdar step's
# (tests/benchmark/bench_tiny_sdar.py).
SDAR_STEP = {"hvd.flash_block_diffusion", "hvd.block_diffusion_noise"}
# Nor a router run apart from its experts, ahead of attention: the tiny
# smallthinker step's (tests/benchmark/bench_tiny_smallthinker.py).
SMALLTHINKER_STEP = {"hvd.moe_route"}
# Nor Mamba-2's chunked scan or experts that work in a latent: the tiny
# nemotron_h step's (tests/benchmark/bench_tiny_nemotron_h.py).
NEMOTRON_STEP = {"hvd.ssd_scan", "hvd.moe_latent"}
OFF_STEP = ({"hvd.layer_norm"} | SPARSE_STEP | AFMOE_STEP | SAMBAY_STEP
            | SDAR_STEP | SMALLTHINKER_STEP | NEMOTRON_STEP)
# The decoder block's names (models/): the programs that hold each. Only
# the mixture decoder rotates, and the tiny sparse step has no dense layer.
BLOCK_STEPS = {"hvd.norm": ("gpt", "sparse", "afmoe", "sambay"),
               "hvd.attn_proj": ("gpt", "sparse", "afmoe", "sambay"),
               "hvd.embed": ("gpt", "sparse", "afmoe", "sambay"),
               "hvd.mlp": ("gpt", "afmoe", "sambay"),
               "hvd.rotary": ("sparse", "afmoe")}
# The indexer is forward only: no gradient reaches it.
DIFFERENTIATED = {"hvd.grad", "hvd.lm_head_loss", "hvd.flash_attention",
                  "hvd.layer_norm", "hvd.sparse_attention", "hvd.moe_ffn",
                  "hvd.flash_window", "hvd.shared_expert",
                  "hvd.flash_block_diffusion", "hvd.moe_route"} | set(
                      BLOCK_STEPS) | SAMBAY_STEP | NEMOTRON_STEP
NESTED_IN = {"hvd.lm_head_loss": "hvd.grad",
             "hvd.flash_attention": "hvd.grad",
             "hvd.sparse_attention": "hvd.grad",
             "hvd.sparse_indexer": "hvd.grad",
             "hvd.moe_ffn": "hvd.grad",
             "hvd.moe_route": "hvd.grad",
             "hvd.flash_window": "hvd.flash_attention",
             "hvd.flash_block_diffusion": "hvd.flash_attention",
             "hvd.shared_expert": "hvd.grad",
             "hvd.ssm": "hvd.grad", "hvd.selective_scan": "hvd.ssm",
             "hvd.gmu": "hvd.grad", "hvd.diff_attention": "hvd.grad",
             "hvd.ssd_scan": "hvd.ssm", "hvd.moe_latent": "hvd.grad",
             **{scope: "hvd.grad" for scope in BLOCK_STEPS},
             "hvd.bucket_pack": "hvd.allreduce_grads",
             "hvd.bucket_allreduce": "hvd.allreduce_grads",
             "hvd.bucket_unpack": "hvd.allreduce_grads"}


def _op_names(text: str) -> list:
    return re.findall(r'op_name="([^"]*)"', text)


STEPS = {"gpt": (gpt_decoder, tiny), "sparse": (sparse_moe_decoder,
                                                tiny_sparse),
         "afmoe": (afmoe, tiny_afmoe), "sambay": (sambay, tiny_sambay),
         "sdar": (sdar_moe, tiny_sdar),
         "smallthinker": (smallthinker, tiny_smallthinker),
         "nemotron_h": (nemotron_h, tiny_nemotron_h)}


def _step_text(step: str, n_devices: int = 1) -> str:
    builder, module = STEPS[step]
    session = builder.build(module.CONFIG, module.JOB,
                            jax.devices()[:n_devices])
    return session.lower(session.abstract_args()).compile().as_text()


@pytest.fixture(scope="module")
def step_texts():
    """The compiled text of the seven tiny steps on one device (the tiny
    GPT step, tests/benchmark/bench_tiny.py, on four too)."""
    try:
        yield {**{step: _step_text(step) for step in STEPS},
               "gpt4": _step_text("gpt", 4)}
    finally:
        hvd.shutdown()     # the builder owns init/shutdown: hand the
        hvd.init()         # other tests their mesh back


@pytest.fixture(scope="module")
def step_names(step_texts):
    """{devices: the op_names of the tiny GPT step's compiled text}."""
    return {1: _op_names(step_texts["gpt"]), 4: _op_names(step_texts["gpt4"])}


@pytest.fixture(scope="module")
def sparse_step_names(step_texts):
    """The op_names of the tiny sparse-attention / routed-experts step
    (tests/benchmark/bench_tiny_sparse.py), one device."""
    return _op_names(step_texts["sparse"])


@pytest.fixture(scope="module")
def afmoe_step_names(step_texts):
    """The op_names of the tiny afmoe step, one device."""
    return _op_names(step_texts["afmoe"])


@pytest.fixture(scope="module")
def sambay_step_names(step_texts):
    """The op_names of the tiny sambay step, one device."""
    return _op_names(step_texts["sambay"])


@pytest.fixture(scope="module")
def sdar_step_names(step_texts):
    """The op_names of the tiny sdar step, one device."""
    return _op_names(step_texts["sdar"])


@pytest.fixture(scope="module")
def smallthinker_step_names(step_texts):
    """The op_names of the tiny smallthinker step, one device."""
    return _op_names(step_texts["smallthinker"])


@pytest.fixture(scope="module")
def nemotron_step_names(step_texts):
    return _op_names(step_texts["nemotron_h"])


@pytest.fixture(scope="module")
def layer_norm_names():
    x = jnp.ones((2, 128, 64), jnp.bfloat16)
    g = jnp.ones((64,), jnp.float32)

    def loss(x, g):
        y, h = ln.ln_residual(x, x, g, g)
        return (y.astype(jnp.float32).sum() + h.astype(jnp.float32).sum())

    return _op_names(jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        x, g).compile().as_text())


def _sparse_attention_loss():
    """(loss, its argument) of one ``sparse_attention`` call at toy
    widths."""
    q = jnp.ones((1, 128, 2, 64), jnp.bfloat16)
    idx = (jnp.ones((1, 128, 2, 8)), jnp.ones((1, 128, 8)),
           jnp.ones((1, 128, 2)))
    return (lambda q: spa.sparse_attention(q, q, q, *idx, topk=16).astype(
        jnp.float32).sum()), q


@pytest.fixture()
def sparse_split_names(monkeypatch):
    """The op_names of one differentiated ``sparse_attention`` call whose
    backward is ``_dq`` + ``_dkv``: the path of a sequence whose dk and dv
    do not fit in VMEM, taken here by a budget of nothing (the tiny
    step's shape takes the fused kernel)."""
    monkeypatch.setattr(spa, "_FUSED_BWD_BUDGET", 0)
    loss, q = _sparse_attention_loss()
    return _op_names(jax.jit(jax.grad(loss)).lower(q).compile().as_text())


@pytest.mark.parametrize("scope", DEVICE_SCOPES)
def test_scope_reaches_the_compiled_program(scope, step_names,
                                            layer_norm_names,
                                            sparse_step_names,
                                            afmoe_step_names,
                                            sambay_step_names,
                                            sdar_step_names,
                                            smallthinker_step_names,
                                            nemotron_step_names):
    by_step = {"gpt": step_names[1], "sparse": sparse_step_names,
               "afmoe": afmoe_step_names, "sambay": sambay_step_names}
    if scope in BLOCK_STEPS:
        programs = {f"{step} decoder": by_step[step]
                    for step in BLOCK_STEPS[scope]}
    elif scope in SPARSE_STEP:
        programs = {"sparse decoder": sparse_step_names}
    elif scope in AFMOE_STEP:
        programs = {"afmoe decoder": afmoe_step_names}
    elif scope in SAMBAY_STEP:
        programs = {"sambay decoder": sambay_step_names}
    elif scope in SDAR_STEP:
        programs = {"sdar decoder": sdar_step_names}
    elif scope in SMALLTHINKER_STEP:
        programs = {"smallthinker decoder": smallthinker_step_names}
    elif scope in NEMOTRON_STEP:
        programs = {"nemotron_h decoder": nemotron_step_names}
    elif scope in OFF_STEP:
        programs = {"layer_norm": layer_norm_names}
    else:
        programs = {f"{n} device(s)": names
                    for n, names in step_names.items()}
    for what, names in programs.items():
        under = [n for n in names if scope in n]
        assert under, f"{scope} is in no op_name of the {what} program"
        if scope in DIFFERENTIATED:
            # JAX marks the direction itself; both must be there.
            assert any("transpose(" in n for n in under), (scope, what)
            assert any("transpose(" not in n for n in under), (scope, what)
        if scope in NESTED_IN:
            assert all(NESTED_IN[scope] in n for n in under), (scope, what)


@pytest.mark.parametrize("kernel, scope", [
    ("hvd_flash_bwd_dq", "hvd.flash_attention"),
    ("hvd_flash_bwd_dkv", "hvd.flash_attention"),
    ("hvd_ln_bwd", "hvd.layer_norm"),
    ("hvd_sparse_attn_bwd", "hvd.sparse_attention"),
    ("hvd_sparse_attn_bwd_dq", "hvd.sparse_attention.split"),
    ("hvd_sparse_attn_bwd_dkv", "hvd.sparse_attention.split"),
    ("hvd_flash_bwd_dq_win", "hvd.flash_window"),
    ("hvd_flash_bwd_dkv_win", "hvd.flash_window"),
    ("hvd_flash_bwd_dq_bd", "hvd.flash_block_diffusion"),
    ("hvd_flash_bwd_dkv_bd", "hvd.flash_block_diffusion")])
def test_custom_vjp_backward_inherits_the_scope(kernel, scope, step_names,
                                                layer_norm_names,
                                                sparse_step_names,
                                                sparse_split_names,
                                                afmoe_step_names,
                                                sdar_step_names):
    """The trap: a scope opened inside the custom_vjp's forward function
    would not reach the backward. In interpret mode a kernel's body is
    traced into the program under its ``name=``. The tiny sparse step
    takes the fused backward kernel (and holds neither of the two);
    ``.split`` is one call on the path of the two."""
    names = {"hvd.layer_norm": layer_norm_names,
             "hvd.sparse_attention": sparse_step_names,
             "hvd.sparse_attention.split": sparse_split_names,
             "hvd.flash_window": afmoe_step_names,
             "hvd.flash_block_diffusion": sdar_step_names}.get(
        scope, step_names[4])
    scope = scope.removesuffix(".split")
    body = [n for n in names if f"{kernel}/" in n or n.endswith(kernel)]
    assert body, f"no op of {kernel} in the program"
    assert all(scope in n and "transpose(" in n for n in body)


def _pallas_names(jaxpr, found: set) -> set:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.add(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_names(sub, found)
    return found


@pytest.fixture(scope="module")
def kernel_names():
    """The name of every pallas_call in forward and backward of the three
    kernel modules of ``horovod_tpu/ops``."""
    q = jnp.ones((1, 128, 2, 64), jnp.bfloat16)
    x = jnp.ones((128, 64), jnp.bfloat16)
    w = jnp.ones((256, 64), jnp.bfloat16)
    lab = jnp.zeros((128,), jnp.int32)
    g = jnp.ones((64,), jnp.float32)
    programs = [
        (lambda q: fa.flash_attention(q, q, q).astype(jnp.float32).sum(),
         q),
        (lambda q: fa.flash_attention(q, q[:, :, :1], q[:, :, :1],
                                      window=32).astype(jnp.float32).sum(),
         q),
        (lambda q: fa.flash_attention(q, q[:, :, :1], q[:, :, :1],
                                      block_diffusion=4).astype(
            jnp.float32).sum(), q),
        _sparse_attention_loss(),
        (lambda x: sx.linear_cross_entropy(x, w, lab).sum(), x),
        (lambda x: ln.ln_residual(x, x, g, g)[0].astype(
            jnp.float32).sum(), x),
        (lambda x: scan.selective_scan(
            x, x * 0.1, -jnp.ones((128, 16)), x[..., :16], x[..., :16],
            g.repeat(2)).sum(), jnp.ones((1, 64, 128), jnp.float32))]
    found: set = set()
    for fn, arg in programs:
        _pallas_names(jax.make_jaxpr(jax.grad(fn))(arg).jaxpr, found)
    # and the sparse backward of a sequence too long for the fused kernel
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(spa, "_FUSED_BWD_BUDGET", 0)
        fn, arg = _sparse_attention_loss()
        _pallas_names(jax.make_jaxpr(jax.grad(fn))(arg).jaxpr, found)
    return found


@pytest.mark.parametrize("kernel", KERNELS)
def test_every_pallas_call_has_an_hvd_name(kernel, kernel_names):
    assert kernel in kernel_names
    assert all(n.startswith("hvd_") for n in kernel_names), kernel_names


def _stripped(text: str) -> str:
    """Compiled HLO text without what a scope may change: the metadata,
    the tables of source locations it points into, and the instruction
    names XLA derives from it."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"(?ms)^(FileNames|FunctionNames|FileLocations|"
                  r"StackFrames)\n.*?\n\n", "", text)
    return re.sub(r"%[A-Za-z_][\w.\-]*", "%", text)


@pytest.mark.parametrize("step", sorted(STEPS))
def test_scopes_are_metadata_only(step, monkeypatch):
    """The optimized program is the same with every scope taken out: the
    names cost nothing at run time. All three tiny steps, so the decoder
    block's names (models/) are among them."""
    # JAX's persistent cache leaves metadata out of its key: with it on,
    # the second compile would be handed the first one's text.
    try:
        with persistent_cache_disabled():
            with_scopes = _step_text(step)
            monkeypatch.setattr(jax, "named_scope",
                                lambda name: contextlib.nullcontext())
            without = _step_text(step)
    finally:
        monkeypatch.undo()
        hvd.shutdown()
        hvd.init()
    for scope in ("hvd.grad", *(s for s, steps in BLOCK_STEPS.items()
                                if step in steps)):
        assert scope in with_scopes and scope not in without, scope
    assert _stripped(with_scopes) == _stripped(without)


# (step, scope, direction): each of the decoder block's names owns some
# instruction of a tiny step in that direction (hlo_owners reads the
# direction off the path: ``remat`` is the forward nn.remat runs again).
OWNED = [("gpt", "hvd.norm", "forward"), ("gpt", "hvd.norm", "backward"),
         ("gpt", "hvd.attn_proj", "forward"),
         ("gpt", "hvd.attn_proj", "backward"),
         ("gpt", "hvd.mlp", "forward"), ("gpt", "hvd.mlp", "backward"),
         ("gpt", "hvd.embed", "forward"), ("gpt", "hvd.embed", "backward"),
         ("sparse", "hvd.rotary", "forward"),
         ("sparse", "hvd.rotary", "remat"),
         ("sparse", "hvd.rotary", "backward"),
         ("sparse", "hvd.norm", "remat"),
         ("sparse", "hvd.attn_proj", "remat"),
         ("sparse", "hvd.embed", "backward"),
         ("afmoe", "hvd.mlp", "remat"), ("afmoe", "hvd.mlp", "backward"),
         ("afmoe", "hvd.rotary", "remat"), ("afmoe", "hvd.norm", "remat"),
         # the router run apart: its matmul is made again in the block's
         # recomputed forward, its gradient comes back through the gates
         ("smallthinker", "hvd.moe_route", "forward"),
         ("smallthinker", "hvd.moe_route", "remat"),
         ("smallthinker", "hvd.moe_route", "backward"),
         # the scan's output and chunk states are kept: it owns instructions
         # forward and backward; the latent projections in each direction
         ("nemotron_h", "hvd.ssd_scan", "forward"),
         ("nemotron_h", "hvd.ssd_scan", "backward"),
         ("nemotron_h", "hvd.moe_latent", "forward"),
         ("nemotron_h", "hvd.moe_latent", "backward"),
         ("nemotron_h", "hvd.shared_expert", "backward"),
         ("nemotron_h", "hvd.ssm", "backward")]


@pytest.fixture(scope="module")
def step_owners(step_texts):
    return {step: hlo_owners.owners(step_texts[step]) for step in STEPS}


@pytest.mark.parametrize("step, scope, direction", OWNED)
def test_block_scope_owns_instructions_in_each_direction(
        step, scope, direction, step_owners):
    owned = step_owners[step]
    whole = [name for name, shares in owned.items()
             if shares == {(scope, direction): 1.0}]
    assert whole, f"no instruction of the {step} step is {scope}'s, " \
                  f"{direction}"
    # the embedding is outside the rematerialised blocks, and no GPT-2
    # cell rematerialises
    if step == "gpt" or scope == "hvd.embed":
        assert not any((scope, "remat") in shares
                       for shares in owned.values())
