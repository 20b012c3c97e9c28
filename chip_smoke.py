#!/usr/bin/env python3
"""Proof that the trainer and the serve engine start on the attached TPU.

    python chip_smoke.py             one chip: train phase, then serve phase
    python chip_smoke.py --chips 4   ONLY data-parallel training over the four
                                     chips of one host (default gradient
                                     allreduce; HOROVOD_OVERLAP=1 in the
                                     environment rehearses the overlap
                                     schedule and its libtpu flags instead),
                                     compared step by step with the same
                                     global batch on one chip
    python chip_smoke.py --rehearse  the same control flow at toy sizes on
                                     whatever JAX finds (the CPU mesh here;
                                     tests/test_chip_smoke.py) — proves paths
                                     and arguments, never the chip

One process, public API only (``horovod_tpu``, ``horovod_tpu.models``,
``horovod_tpu.serve``). Without ``--rehearse`` anything but a TPU, or an
exception in any phase, is a non-zero exit with no result line. On success
the LAST line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Everything above it is information for the reader — compile seconds, step
milliseconds and peak memory are printed so a bring-up can be judged, and
are not speed results: nothing printed here belongs in README or PERF.md as
a rate.

Train phase: GPT-350M (24 layers, 16 heads, d_model 1024, d_ff 4096,
vocab 32000, seq 1024, bf16, Pallas flash attention, LM head through
``lm_head_loss(mode="auto")``), per-chip batch 8, the step the benchmark's
``benchmarks/builders/gpt_decoder.py`` builds: ``hvd.DistributedOptimizer``
inside ``hvd.shard_map`` over ``hvd.mesh()`` with donated state. Serve phase: ``GenerationEngine``
on a GPT-124M-width model through ``submit``/``run``, greedy tokens checked
against a plain full-recompute decode.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

SEED = 0

# --chips 4: bf16 activations and a different reduction order (four
# per-chip means averaged by the allreduce vs four micro-batch means
# averaged on one chip) move the loss in its 4th significant digit; a
# wrong mesh or a batch that landed on one device moves it in the 2nd.
# The total decrease is compared too: a gradient scaled by the world size
# (or its inverse) keeps early losses close and the slope far off.
DP_LOSS_ATOL = 1e-2
DP_DECREASE_RTOL = 0.2

# Serve parity: with random weights neighbouring logits can sit closer
# than bf16 resolves, and the paged decode sums in another order than the
# full forward. A token that differs from the reference argmax must be a
# tie at that resolution, or the check fails.
GREEDY_TIE_ATOL = 5e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    # train (GPT-350M)
    num_layers: int = 24
    num_heads: int = 16
    d_model: int = 1024
    d_ff: int = 4096
    vocab_size: int = 32000
    seq_len: int = 1024
    per_chip_batch: int = 8
    train_steps: int = 6
    # serve (models.gpt_small)
    serve_requests: int = 8
    prompt_lens: tuple = (128, 512)
    new_tokens: int = 32
    page_size: int = 16
    serve_overrides: dict = dataclasses.field(default_factory=dict)


FULL = Sizes()
TINY = Sizes(num_layers=2, num_heads=4, d_model=64, d_ff=128,
             vocab_size=256, seq_len=128, per_chip_batch=2,
             prompt_lens=(6, 20), new_tokens=4, page_size=4,
             serve_overrides=dict(num_layers=2, num_heads=4, d_model=64,
                                  d_ff=128, vocab_size=256))


def log(msg: str) -> None:
    print(msg, flush=True)


def _files_under(root: str) -> int:
    return sum(len(fs) for _, _, fs in os.walk(root))


# ---------------------------------------------------------------------------
# train


def train_phase(devices, sizes: Sizes, global_batch: int, micro: int = 1):
    """``sizes.train_steps`` steps of the README step on ``devices`` over one
    fixed batch. ``micro`` > 1 scans the per-chip batch in that many
    micro-batches and averages their gradients (the one-chip reference of
    --chips 4, whose global batch does not fit one chip in one piece).
    Returns (losses, compiled_text, step_outputs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import GPT, GPTConfig

    hvd.shutdown()
    hvd.init(devices=devices, mesh_shape=(1, len(devices)))
    mesh = hvd.mesh()
    on_tpu = devices[0].platform == "tpu"
    # The default gradient allreduce unless the caller exported
    # HOROVOD_OVERLAP=1 (bucket streaming + libtpu's async-collective
    # flags): say which schedule the step below was traced with.
    log(f"[train] world={hvd.size()} mesh={dict(mesh.shape)} "
        f"global_batch={global_batch} micro_batches={micro} "
        f"overlap={hvd.describe_plan().overlap}")

    cfg = GPTConfig(vocab_size=sizes.vocab_size, max_seq_len=sizes.seq_len,
                    attention="flash", num_layers=sizes.num_layers,
                    num_heads=sizes.num_heads, d_model=sizes.d_model,
                    d_ff=sizes.d_ff, return_hidden=True)
    model = GPT(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(
        jax.random.PRNGKey(SEED),
        jnp.zeros((1, sizes.seq_len), jnp.int32))["params"]
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    log(f"[train] GPT {sizes.num_layers}L/{sizes.d_model}d/"
        f"{sizes.num_heads}h vocab {sizes.vocab_size} seq {sizes.seq_len}: "
        f"{n_params / 1e6:.1f}M params, init {time.perf_counter() - t0:.1f}s")

    rs = np.random.RandomState(SEED)
    toks = rs.randint(0, sizes.vocab_size,
                      (global_batch, sizes.seq_len + 1))
    xb, yb = toks[:, :-1], toks[:, 1:]

    def loss_fn(p, x, y):
        h = model.apply({"params": p}, x)
        return hvd.lm_head_loss(h, p["wte"].astype(cfg.dtype), y,
                                mode="auto").mean()

    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
    opt_state = tx.init(params)

    # reduce=False: the gradients stay per-rank locals and the optimizer's
    # fused bucket allreduce is the one gradient collective. (Left to
    # reduce too, the tape averages them and the optimizer, which reads a
    # replicated gradient as autodiff's cross-rank SUM, divides by the
    # world size a second time — invisible on one chip.)
    local_grads = hvd.value_and_grad(loss_fn, reduce=False)

    def spmd(p, s, x, y):
        if micro == 1:
            loss, grads = local_grads(p, x, y)
        else:
            xs = x.reshape(micro, -1, x.shape[-1])
            ys = y.reshape(micro, -1, y.shape[-1])

            def body(acc, xy):
                return jax.tree.map(jnp.add, acc, local_grads(p, *xy)), None

            # The running sum is per-rank like what it accumulates.
            zero = jax.tree.map(
                lambda a: jax.lax.pcast(jnp.zeros_like(a), hvd.HVD_AXES,
                                        to="varying"),
                (jnp.float32(0), p))
            (loss, grads), _ = jax.lax.scan(body, zero, (xs, ys))
            loss, grads = jax.tree.map(lambda a: a / micro, (loss, grads))
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), s, hvd.allreduce(loss)

    step = jax.jit(hvd.shard_map(
        spmd, mesh=mesh,
        in_specs=(P(), P(), hvd.data_pspec(), hvd.data_pspec()),
        out_specs=(P(), P(), P())), donate_argnums=(0, 1))

    rep = NamedSharding(mesh, P())
    params = jax.device_put(params, rep)
    opt_state = jax.device_put(opt_state, rep)
    xb = jax.device_put(jnp.asarray(xb), hvd.data_sharding())
    yb = jax.device_put(jnp.asarray(yb), hvd.data_sharding())

    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, xb, yb).compile()
    log(f"[train] compile (incl. kernel sweep on a cold cache): "
        f"{time.perf_counter() - t0:.1f}s")
    text = compiled.as_text()
    if on_tpu and "tpu_custom_call" not in text:
        raise RuntimeError(
            "the compiled train step holds no tpu_custom_call: flash "
            "attention gave way to the dense path")

    losses = []
    for i in range(sizes.train_steps + 1):   # step 0 warms, then >= 6
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, xb, yb)
        jax.block_until_ready((params, opt_state, loss))
        ms = (time.perf_counter() - t0) * 1e3
        losses.append(float(loss))
        log(f"[train] step {i}: loss {losses[-1]:.4f}  {ms:.1f} ms")
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not decrease: {losses}")
    for d in devices:
        stats = d.memory_stats() or {}
        log(f"[train] {d}: peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use', 'n/a')} bytes_in_use "
            f"{stats.get('bytes_in_use', 'n/a')}")
    return losses, text, (params, opt_state, loss)


def four_chip_phase(devices, sizes: Sizes) -> None:
    """Data-parallel training over every device vs the same global batch
    and seed on ``devices[:1]``, plus the proof that the work is spread."""
    import jax
    import numpy as np

    n = len(devices)
    global_batch = sizes.per_chip_batch * n
    dp_losses, text, outs = train_phase(devices, sizes, global_batch)
    spread = {d for leaf in jax.tree.leaves(outs) for d in leaf.devices()}
    if spread != set(devices):
        raise RuntimeError(f"step outputs live on {sorted(map(str, spread))}"
                           f", not on all {n} devices")
    if devices[0].platform == "tpu":
        idle = [str(d) for d in devices
                if not (d.memory_stats() or {}).get("bytes_in_use")]
        if idle:
            raise RuntimeError(f"devices holding no bytes: {idle}")
    if "all-reduce" not in text:
        raise RuntimeError("no all-reduce in the compiled DP step")
    log(f"[4chip] outputs on {len(spread)} devices, every device holds "
        f"bytes, all-reduce present; LIBTPU_INIT_ARGS="
        f"{os.environ.get('LIBTPU_INIT_ARGS', '')!r}")
    del outs

    # Half a chip's batch per micro-batch: the compiler's memory analysis
    # puts the one-chip program at 10.0 GB that way and at 14.1 GB with
    # micro-batches of a whole per-chip batch — too near the 16 GB chip.
    ref_losses, _, _ = train_phase(devices[:1], sizes, global_batch,
                                   micro=2 * n)
    diffs = np.abs(np.array(dp_losses) - np.array(ref_losses))
    log(f"[4chip] DP losses  {[round(v, 4) for v in dp_losses]}")
    log(f"[4chip] ref losses {[round(v, 4) for v in ref_losses]}")
    log(f"[4chip] max |diff| {diffs.max():.5f} (tolerance {DP_LOSS_ATOL})")
    if diffs.max() > DP_LOSS_ATOL:
        raise RuntimeError("data-parallel losses diverge from the one-chip "
                           f"reference: {diffs.tolist()}")
    drop, ref_drop = (v[0] - v[-1] for v in (dp_losses, ref_losses))
    log(f"[4chip] decrease {drop:.5f} vs {ref_drop:.5f} "
        f"(rtol {DP_DECREASE_RTOL})")
    if abs(drop - ref_drop) > DP_DECREASE_RTOL * ref_drop:
        raise RuntimeError("data-parallel loss decrease is off the one-chip "
                           "reference's: a gradient is mis-scaled")


# ---------------------------------------------------------------------------
# serve


def serve_phase(device, sizes: Sizes) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.models import GPT, gpt_small
    from horovod_tpu.serve import GenerationEngine, PageConfig, Request

    hvd.shutdown()
    hvd.init(devices=[device])
    lo, hi = sizes.prompt_lens
    longest = hi + sizes.new_tokens
    cfg = gpt_small(**{"vocab_size": 32000, "max_seq_len": longest,
                       **sizes.serve_overrides})
    model = GPT(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED + 1),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    pages_per_slot = -(-longest // sizes.page_size)
    pc = PageConfig(num_pages=sizes.serve_requests * pages_per_slot + 1,
                    page_size=sizes.page_size,
                    max_slots=sizes.serve_requests,
                    pages_per_slot=pages_per_slot,
                    num_layers=cfg.num_layers, num_heads=cfg.num_heads,
                    head_dim=cfg.d_model // cfg.num_heads, dtype=cfg.dtype)
    compiles0 = hvd.compile.stats()
    t0 = time.perf_counter()
    # eos_id=-1: no token ends a request early, so each yields new_tokens.
    engine = GenerationEngine(cfg, params, pc, devices=[device], eos_id=-1)
    compiles1 = hvd.compile.stats()
    if compiles1["hits"] + compiles1["misses"] == \
            compiles0["hits"] + compiles0["misses"]:
        raise RuntimeError("the engine's step executable did not come "
                           "through the compile registry (AOT warm failed)")
    log(f"[serve] GPT {cfg.num_layers}L/{cfg.d_model}d vocab "
        f"{cfg.vocab_size}, {pc.max_slots} slots x {pc.pages_per_slot} "
        f"pages of {pc.page_size}: engine ready in "
        f"{time.perf_counter() - t0:.1f}s")

    rs = np.random.RandomState(SEED + 2)
    lens = np.linspace(lo, hi, sizes.serve_requests).astype(int)
    requests = [Request(prompt=rs.randint(2, cfg.vocab_size, n).tolist(),
                        max_new_tokens=sizes.new_tokens) for n in lens]
    prompts = [list(r.prompt) for r in requests]
    for r in requests:
        engine.submit(r)
    t0 = time.perf_counter()
    stats = engine.run()
    log(f"[serve] {len(stats.completed)}/{len(requests)} requests finished "
        f"in {stats.steps} engine steps, {time.perf_counter() - t0:.1f}s "
        f"(prefill {stats.prefill_tokens} tok, decode "
        f"{stats.decode_tokens} tok)")
    if len(stats.completed) != len(requests):
        raise RuntimeError("not every request finished")
    for r in requests:
        if len(r.generated) != sizes.new_tokens:
            raise RuntimeError(f"req {r.req_id}: {len(r.generated)} tokens, "
                               f"expected {sizes.new_tokens}")

    # Plain reference for the longest request: full recompute of the whole
    # context every token, dense attention, one padded shape.
    ref_model = GPT(dataclasses.replace(cfg, attention="dense"))
    full = jax.jit(lambda p, t: ref_model.apply({"params": p}, t)[0])
    req, prompt = requests[-1], prompts[-1]
    ctx = np.zeros((1, longest), np.int32)
    ctx[0, :len(prompt)] = prompt
    exact = ties = 0
    for i, tok in enumerate(req.generated):
        n = len(prompt) + i
        row = np.asarray(full(params, jnp.asarray(ctx))[n - 1], np.float32)
        gap = float(row.max() - row[tok])
        if tok == int(row.argmax()):
            exact += 1
        elif gap <= GREEDY_TIE_ATOL:
            ties += 1
        else:
            raise RuntimeError(
                f"greedy mismatch at new token {i}: engine {tok} (logit "
                f"{row[tok]:.4f}) vs reference {int(row.argmax())} "
                f"({row.max():.4f})")
        ctx[0, n] = tok   # continue from what the engine emitted
    log(f"[serve] greedy parity vs full recompute (prompt {len(prompt)}): "
        f"{exact}/{len(req.generated)} identical, {ties} ties within "
        f"{GREEDY_TIE_ATOL}")


# ---------------------------------------------------------------------------
# entry


def run(args) -> dict:
    from importlib import metadata

    import jax
    import jaxlib

    import horovod_tpu as hvd

    # First touch of JAX: init arms the compile cache and, where the
    # caller exported HOROVOD_OVERLAP=1, the overlap flags; both must
    # precede the first backend.
    hvd.init()
    sizes = TINY if args.rehearse else FULL
    devices = jax.devices()
    dev = devices[0]

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    if dev.platform != "tpu" and not args.rehearse:
        raise RuntimeError(
            f"JAX found no TPU (platform {dev.platform!r}); chip_smoke "
            "runs on the chip or fails (--rehearse is the CPU rehearsal)")
    if len(devices) < args.chips:
        raise RuntimeError(f"--chips {args.chips} but JAX reports "
                           f"{len(devices)} device(s)")
    if args.chips == 1:
        devices = devices[:1]

    cache_root = hvd.compile.cache_dir()
    before = _files_under(cache_root)
    if args.chips == 4:
        four_chip_phase(devices[:4], sizes)
    else:
        global_batch = sizes.per_chip_batch
        train_phase(devices, sizes, global_batch)
        serve_phase(devices[0], sizes)
    hvd.shutdown()

    armed = jax.config.jax_compilation_cache_dir
    if armed != cache_root:
        raise RuntimeError(f"compile cache not armed: jax has {armed!r}, "
                           f"expected {cache_root!r}")
    after = _files_under(cache_root)
    placed = ("JAX_COMPILATION_CACHE_DIR"
              if os.environ.get("JAX_COMPILATION_CACHE_DIR")
              else "checkout default")
    log(f"[cache] {cache_root} ({placed}): {after} files, "
        f"{after - before} written by this run")
    if after == 0:
        raise RuntimeError("the run left nothing in the compile cache")
    tuned = os.path.join(cache_root, "kernel_autotune.json")
    if os.path.exists(tuned):
        with open(tuned) as f:
            for key, e in sorted(json.load(f).items()):
                log(f"[autotune] {key}: blocks {e.get('blocks')} "
                    f"{1e3 * e.get('seconds_per_call', 0):.3f} ms/call, "
                    f"swept in {e.get('sweep_seconds')}s")
    elif dev.platform == "tpu":
        raise RuntimeError("no kernel sweep was recorded on the chip")
    native = getattr(sys.modules.get("horovod_tpu.cc"), "_lib", None)
    log(f"[native] libhvdtpu.so {'LOADED' if native else 'not loaded'} "
        "(single-process phases do not need it)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on whatever JAX finds; not a chip run")
    args = ap.parse_args(argv)
    try:
        device = run(args)
    except BaseException:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
