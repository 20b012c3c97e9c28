#!/usr/bin/env python3
"""Where a block-diffusion cell's ``loss_gap`` at its SECOND checked step
comes from, on one seed.

    chiprun -- python3 scripts/sdar_loss_gap.py --workload sdar-30b-a3b.train-8k-1chip --seeds <n>[,<n>...]

The first step's loss reads the forward pass alone; the second reads it
through parameters that one AdamW step has moved, and AdamW's first step is
``lr * g / (|g| + eps)``: a SIGN, so an element whose gradient lies within
the program's rounding of zero moves by the whole ``lr`` one way in the
program and the other way in the reference. This script splits the second
step's gap on a seed into

* ``forward``: program loss - float32 loss, both AT THE PROGRAM'S parameters
  (the bfloat16 forward pass alone);
* ``parameters``: float32 loss at the program's parameters - float32 loss at
  the reference's (what the first step's rounding did to the weights);

names the positions that carry the second part (each beside its weight
``1 / t``), the share of elements a leaf group moved the other way, to
first order the part of the gap each leaf group carries
(``g1 . (theta_program - theta_reference)``), the gradient's norm at both
steps (how steep the loss is where the second step reads it), and what
``loss_gap`` would read under three faults it is held against (the first
step's noise again, no weight, half the positions left out).

Not part of the benchmark: PERF.md section 6 (PR 41) cites its output,
``chiprun_out/<dir>/loss_gap_<seed>.json``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def position_losses(params, xt, x0, s, q_block):
    """float32 CE of every position of the noised half, one row at a time:
    [rows, L]. The reference's own layers (lib/reference_sdar.py)."""
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import reference_sdar as ref

    mm = ref._mm("float32")
    L = x0.shape[1]
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                           *(params[f"h{i}"] for i in range(s["layers"])))
    block = functools.partial(ref._block, s=s, mm=mm, q_block=q_block)

    def row(_, args):
        noised, clean = args
        x = params["embed"][jnp.concatenate([noised, clean])]
        x, _ = jax.lax.scan(lambda h, p: (block(h, p), None), x, stacked)
        x = ref.rms_norm(x[:L], params["ln_f"]["scale"], s["eps"])
        logp = jax.nn.log_softmax(mm("tc,vc->tv", x, params["head"]), -1)
        return None, -jnp.take_along_axis(logp, clean[:, None], -1)[:, 0]

    return jax.lax.scan(row, None, (xt, x0))[1]


def first_adamw_step(p, g, opt):
    """The reference's first step (lib/reference_sdar.py ``train_steps``
    at t = 1, where the moments' corrections cancel)."""
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import reference_sdar as ref

    gnorm = jnp.sqrt(sum(n ** 2 for n in ref.leaf_norms(g).values()))
    clip = jnp.where(gnorm < opt["clip_norm"], 1.0, opt["clip_norm"] / gnorm)
    return jax.tree.map(
        lambda w, a: w - opt["lr"] * (
            clip * a / (jnp.abs(clip * a) + opt["eps"])
            + opt["weight_decay"] * w), p, g), gnorm


def group_of(path: str) -> str:
    parts = path.split("/")
    return parts[0] if len(parts) < 3 else "/".join(parts[1:])


def one_seed(session, seed: int, out_dir: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.lib import reference_sdar as ref

    s, opt = session.sizes, session.opt
    q_block = session.config["reference"]["q_block"]
    session.init_state(seed)
    session.place_inputs(seed)
    if session.compiled is None:
        session.compile()
    loss_p0 = float(session.step())
    theta_p = jax.device_get(session.params)
    loss_p1 = float(session.step())
    session.release()

    dev = session.devices[0]
    seed32 = jax.device_put(session._seed(seed), dev)
    toks = jax.device_put(session._token_pool(seed)[:2], dev)
    x = [toks[i][:, :-1] for i in range(2)]
    loss_of = functools.partial(ref.loss_sum, s=s, q_block=q_block)
    rows, L = x[0].shape

    @jax.jit
    def reference_step(seed32, x0):
        p0 = ref.make_params(seed32, s)
        xt, masked, t = ref.noise(seed32, 0, x0, s)
        loss, g = jax.value_and_grad(loss_of)(p0, xt, x0, masked, t)
        theta, gnorm = first_adamw_step(
            p0, jax.tree.map(lambda a: a / rows, g), opt)
        return loss / rows, gnorm, theta

    loss_r0, gnorm0, theta_r = reference_step(seed32, x[0])

    @jax.jit
    def second_step(seed32, theta_r, theta_p, x0):
        xt, masked, t = ref.noise(seed32, 1, x0, s)
        weight = jnp.where(masked, 1.0 / t, 0.0)
        ce_r = position_losses(theta_r, xt, x0, s, q_block)
        ce_p = position_losses(theta_p, xt, x0, s, q_block)
        # The second step under the FIRST step's noise: what a step that
        # never folds its count into the key would read.
        xt0, masked0, t0 = ref.noise(seed32, 0, x0, s)
        same_noise = (position_losses(theta_r, xt0, x0, s, q_block)
                      * jnp.where(masked0, 1.0 / t0, 0.0)).sum(1).mean() / L
        loss, g1 = jax.value_and_grad(loss_of)(theta_r, xt, x0, masked, t)
        first_order = ref.path_dict(jax.tree.map(
            lambda g, a, b: jnp.sum(g * (a - b)) / rows,
            g1, theta_p, theta_r))
        g1_norm = jnp.sqrt(sum(
            n ** 2 for n in ref.leaf_norms(g1).values())) / rows
        return (loss / rows, g1_norm, weight, ce_r, ce_p, first_order,
                same_noise)

    @jax.jit
    def moved_the_other_way(seed32, theta_r, theta_p):
        return ref.path_dict(jax.tree.map(
            lambda a, b, c: jnp.mean(
                (jnp.sign(a - c) != jnp.sign(b - c)).astype(jnp.float32)),
            theta_p, theta_r, ref.make_params(seed32, s)))

    theta_p = jax.device_put(theta_p, dev)
    other_way = jax.device_get(moved_the_other_way(seed32, theta_r, theta_p))
    (loss_r1, g1_norm, weight, ce_r, ce_p, first_order,
     same_noise) = jax.device_get(
        second_step(seed32, theta_r, theta_p, x[1]))
    loss_f32_at_p = float((ce_p * weight).sum(1).mean() / L)
    loss_f32_at_r = float((ce_r * weight).sum(1).mean() / L)
    part = ((ce_p - ce_r) * weight / (L * rows)).reshape(-1)
    order = np.argsort(-np.abs(part))[:12]
    groups: dict = {}
    sizes = ref.path_dict(jax.tree.map(lambda a: a.size, theta_r))
    for path, value in first_order.items():
        g = groups.setdefault(group_of(path), [0.0, 0.0, 0])
        g[0] += float(value)
        g[1] += float(other_way[path]) * sizes[path]
        g[2] += sizes[path]
    row = {
        "seed": seed,
        "loss_program": [loss_p0, loss_p1],
        "loss_reference": [float(loss_r0), float(loss_r1)],
        "gap": [abs(loss_p0 - float(loss_r0)), abs(loss_p1 - float(loss_r1))],
        "step1_float32_loss_at_program_parameters": loss_f32_at_p,
        "step1_float32_loss_at_reference_parameters": loss_f32_at_r,
        "step1_forward_part": loss_p1 - loss_f32_at_p,
        "step1_parameters_part": loss_f32_at_p - loss_f32_at_r,
        # What the second step's loss reads under the faults the number is
        # held against, in float32 at the reference's parameters: its gap
        # to the sound loss is what ``loss_gap`` would read.
        "step1_loss_gap_under_a_fault": {
            "the_first_steps_noise_again": abs(
                float(same_noise) - loss_f32_at_r),
            "no_weight": abs(float((ce_r * (weight > 0)).sum(1).mean() / L)
                             - loss_f32_at_r),
            "second_half_of_the_positions_left_out": abs(float(
                (ce_r * weight)[:, :L // 2].sum(1).mean() / L)
                - loss_f32_at_r)},
        "grad_norm_step0": float(gnorm0),
        "grad_norm_step1": float(g1_norm),
        "step1_masked_positions": int((weight > 0).sum()),
        "step1_largest_weights": [
            float(w) for w in np.sort(weight.reshape(-1))[-5:][::-1]],
        "step1_positions_by_part": [
            {"position": int(i), "weight": float(weight.reshape(-1)[i]),
             "ce_at_program": float(ce_p.reshape(-1)[i]),
             "ce_at_reference": float(ce_r.reshape(-1)[i]),
             "part": float(part[i])} for i in order],
        "step1_part_abs_sum": float(np.abs(part).sum()),
        "step1_ce_difference_rms_masked": float(np.sqrt(np.mean(
            (ce_p - ce_r)[weight > 0] ** 2))),
        "leaf_groups": {
            k: {"first_order_part": v[0], "moved_the_other_way": v[1] / v[2]}
            for k, v in sorted(groups.items())},
        "first_order_sum": float(sum(first_order.values())),
    }
    print(json.dumps(row), flush=True)
    with open(os.path.join(out_dir, f"loss_gap_{seed}.json"), "w") as f:
        json.dump(row, f, indent=1)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--dir", default="loss_gap")
    ap.add_argument("--root", default=ROOT,
                    help="the benchmark root (tests hand in a tiny one)")
    ap.add_argument("--any-device", action="store_true",
                    help="run where no TPU is (a tiny root, on the CPU)")
    args = ap.parse_args(argv)
    from benchmarks import run as cli
    from benchmarks.lib import manifest as mf

    if not args.any_device:
        cli.place_cache()

    manifest = mf.load(args.root)
    cell = mf.cell(manifest, args.workload)
    if args.any_device:
        import jax
        devices = jax.devices()[:cell["chips"]]
    else:
        devices = cli.chips_or_none(cell["chips"])
        if devices is None:
            return cli.NO_CHIP
    config = mf.config_of(manifest, cell["config"], args.root)
    session = mf.load_module("builders", config["builder"]).build(
        config, mf.job_of(cell["traffic"], args.root), devices)
    out_dir = os.path.join(ROOT, "chiprun_out", args.dir)
    os.makedirs(out_dir, exist_ok=True)
    for seed in (int(v) for v in args.seeds.split(",") if v):
        one_seed(session, seed, out_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
