#!/usr/bin/env python
"""How close the picks of a decoder cell's expert layers stand to a flip
between the program and the benchmark's plain reference.

    python scripts/mla_moe_routing_check.py [--cell NAME] [--seeds 8] [--tiny]
                                            [--out chiprun_out/routing.json]

(Named for the first family it read, ``--model mla_moe``, the default cell;
``--cell`` takes any cell of ``--model mla_moe`` or ``--model hybrid_ssm_moe``.
Below, 8 and 9 stand for a cell's ``--moe-top-k`` and the next.)

The comparison that decides ``correct`` holds the program's gradients to the
reference's on one window.  Both sides pick each token's 8 experts from
their OWN router scores, which differ by rounding (another attention, another
summation order); where a token's 8th and 9th score lie closer than that
difference the two sides may pick differently, and if one of the two experts
is held here a gradient leaf moves by far more than any tolerance.  For each
seed this records, over every expert layer (the prediction module's
included) of the cell's compared sample (one window of 4,096 tokens, fresh
weights, "highest" matmul precision):

- ``min_margin``: the smallest distance between a token's 8th and 9th
  largest score in the reference;
- ``max_score_diff``: the largest |program - reference| over all scores;
- ``flipped_tokens``: tokens whose picked sets differ, and how many of those
  differ in an expert held here; ``flips`` names the first of them (layer,
  token, the reference's margin there);
- ``near_ties``: decisions whose margin lies under 1e-6 / 1e-5 / 1e-4, and
  how many of them have a held expert as 8th or 9th: the density of margins
  near 0.  With ``rms_margin_diff`` (how far the program's margin between
  the reference's 8th and 9th expert stands from the reference's own) the
  share of runs that fail by a flip follows.

Needs a TPU at the cell's size; ``--tiny`` runs toy widths anywhere (the
CPU test's rehearsal).  Sizes and the data come from the benchmark's own
files for the cell (default ``joyai_flash_train_t4096_1chip``).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

CELL = "joyai_flash_train_t4096_1chip"
# margins counted as near a tie, and how many flipped tokens a layer names
NEAR = (1e-6, 1e-5, 1e-4)
MAX_NAMED = 8


def layer_scores(blocks, x, attention, norm, block):
    """Router scores of every expert layer: ``blocks`` are the layers'
    parameter dicts in order, ``x`` the embedded input; ``attention``,
    ``norm`` and ``block`` are one side's own functions."""
    import jax

    scores = []
    for p in blocks:
        if "router" in p["ffn"]:
            after_attention = x + attention(
                p["attn"], norm(x, p["attn_norm"]))
            routed = norm(after_attention, p["ffn_norm"])
            scores.append(jax.nn.sigmoid(
                routed.reshape(-1, routed.shape[-1]) @ p["ffn"]["router"]))
        x = block(p, x)
    return x, scores


def all_scores(params, tokens, attention, norm, block):
    """Scores of the main model's expert layers and the prediction
    module's, on the window's inputs."""
    import jax.numpy as jnp

    inputs = tokens[:, :-1]
    h, scores = layer_scores(
        params["layers"], params["embed"][inputs], attention, norm, block)
    if "mtp" in params:
        p = params["mtp"]
        merged = jnp.concatenate(
            [norm(params["embed"][tokens[:, 1:]], p["embed_norm"]),
             norm(h, p["hidden_norm"])], axis=-1)
        scores += layer_scores(
            [p["block"]], merged @ p["w_eh"], attention, norm, block)[1]
    return scores


def part_scores(params, tokens, norm, part):
    """Router scores of every expert layer of a model built from a pattern
    of residual parts (``--model hybrid_ssm_moe``): ``norm`` and ``part``
    are one side's own functions, ``part(i, p, x)`` the stream after part
    ``i``."""
    import jax

    x, scores = params["embed"][tokens[:, :-1]], []
    for i, p in enumerate(params["layers"]):
        if "router" in p["mixer"]:
            routed = norm(x, p["norm"])
            scores.append(jax.nn.sigmoid(
                routed.reshape(-1, routed.shape[-1]) @ p["mixer"]["router"]))
        x = part(i, p, x)
    return scores


def score_programs(model, reference):
    """``(system, plain)``: each ``(params, tokens) -> [scores a layer]``,
    for either decoder family and its reference module."""
    import inspect

    from pytorch_distributed_rnn_tpu.models.decoder_common import rms_norm

    def norm(x, w):
        return rms_norm(x, w, model.norm_eps)

    if hasattr(model, "pattern"):
        # the reference's part takes, after (p, x), what its lm_loss takes
        # after (params, batch): the share's first expert, then the
        # published constants it defaults to
        step = getattr(reference, "part", None) or reference.layer
        constants = [
            value.default for value in list(inspect.signature(
                reference.lm_loss).parameters.values())[3:]]
        return (
            lambda p, t: part_scores(
                p, t, norm, lambda i, p_, x: model._layer(
                    model.pattern[i], p_, x)[0]),
            lambda p, t: part_scores(
                p, t, reference.rms_norm, lambda i, p_, x: step(
                    p_, x, model.experts_first, *constants)))
    return (
        lambda p, t: all_scores(
            p, t, model._attention, norm,
            lambda p_, x: model._block(p_, x)[0]),
        lambda p, t: all_scores(
            p, t, reference.latent_attention, reference.rms_norm,
            lambda p_, x: reference.block(p_, x, model.experts_first)))


def make_report(model, reference):
    """``report(params, tokens) -> dict`` of the numbers above for
    one model and one reference module; the two score programs are
    compiled once."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    system_scores, plain_scores = map(
        jax.jit, score_programs(model, reference))
    k = model.num_selected
    lo, hi = model.experts_first, model.experts_first + model.held

    def report(params, tokens) -> dict:
        with jax.default_matmul_precision("highest"):
            system = system_scores(params, tokens)
            plain = plain_scores(params, tokens)
        out = {"min_margin": float("inf"), "max_score_diff": 0.0,
               "flipped_tokens": 0, "flipped_tokens_held_here": 0,
               "decisions": 0, "rms_score_diff_by_layer": [],
               "rms_margin_diff_by_layer": [],
               "near_ties": {str(m): [0, 0] for m in NEAR}, "flips": []}
        for layer, (got, want) in enumerate(zip(system, plain, strict=True)):
            out["rms_score_diff_by_layer"].append(
                float(jnp.sqrt(jnp.mean(jnp.square(got - want)))))
            top, picked_want = jax.lax.top_k(want, k + 1)
            margin = top[:, k - 1] - top[:, k]
            out["min_margin"] = min(out["min_margin"], float(jnp.min(margin)))
            boundary = picked_want[:, k - 1:]
            # the program's margin between the same two experts
            own = jnp.take_along_axis(got, boundary, axis=1)
            out["rms_margin_diff_by_layer"].append(float(jnp.sqrt(jnp.mean(
                jnp.square(own[:, 0] - own[:, 1] - margin)))))
            at_held = jnp.any((boundary >= lo) & (boundary < hi), axis=1)
            for m in NEAR:
                out["near_ties"][str(m)][0] += int(jnp.sum(margin < m))
                out["near_ties"][str(m)][1] += int(
                    jnp.sum((margin < m) & at_held))
            out["max_score_diff"] = max(
                out["max_score_diff"], float(jnp.max(jnp.abs(got - want))))
            _, picked_got = jax.lax.top_k(got, k)
            rows = jnp.arange(got.shape[0])[:, None]
            mask_got = jnp.zeros(got.shape, bool).at[
                rows, picked_got].set(True)
            mask_want = jnp.zeros(got.shape, bool).at[
                rows, picked_want[:, :k]].set(True)
            differs = mask_got != mask_want
            flipped = jnp.any(differs, axis=1)
            held = jnp.any(differs[:, lo:hi], axis=1)
            out["flipped_tokens"] += int(jnp.sum(flipped))
            out["flipped_tokens_held_here"] += int(jnp.sum(held))
            for token in np.flatnonzero(np.asarray(flipped))[:MAX_NAMED]:
                out["flips"].append({
                    "layer": layer, "token": int(token),
                    "margin": float(margin[token]),
                    "held_here": bool(held[token])})
            out["decisions"] += int(got.shape[0])
        return out

    return report


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="mla_moe_routing_check.py")
    parser.add_argument("--cell", default=CELL,
                        help="a decoder cell of BENCHMARK.json")
    parser.add_argument("--seeds", type=int, default=8)
    parser.add_argument("--first-seed", type=int, default=2147483700)
    parser.add_argument("--seed-list", default=None,
                        help="comma-separated seeds, in place of --seeds "
                             "from --first-seed")
    parser.add_argument("--tiny", action="store_true",
                        help="the tests' stand-in sizes (runs anywhere)")
    parser.add_argument("--out", default="chiprun_out/routing_check.json")
    args = parser.parse_args(argv)

    from pytorch_distributed_rnn_tpu.utils import apply_platform_overrides

    jax = apply_platform_overrides()
    from benchmarks import correctness, datagen, harness
    from pytorch_distributed_rnn_tpu.main import build_parser
    from pytorch_distributed_rnn_tpu.training import families

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        print("mla_moe_routing_check: the cell's size needs a TPU "
              "(--tiny runs toy widths)", file=sys.stderr)
        return 1
    cell = harness.load_cell(args.cell)
    if args.tiny:
        data = harness.BENCH_DIR / "tests" / "data"
        cell["config"] = json.loads(
            (data / "configs" / f"{cell['config']['name']}.json").read_text())
    config = cell["config"]
    reference = correctness.load_module(
        harness.BENCH_DIR / config["reference"]["file"])
    rows, report = [], None
    seeds = ([int(s) for s in args.seed_list.split(",")] if args.seed_list
             else range(args.first_seed, args.first_seed + args.seeds))
    for seed in seeds:
        cli = build_parser().parse_args(
            [*config["cli"], *cell["traffic"]["cli"], "--seed", str(seed),
             cell["traffic"]["strategy"]])
        windows = datagen.make_splits(
            config["dataset"], cell["traffic"], seed)[0][0]
        from pytorch_distributed_rnn_tpu.data.text import TextDataset

        model = families.build_model(cli, TextDataset(windows))
        report = report or make_report(model, reference)
        params = model.init(jax.random.PRNGKey(seed + 1))
        tokens = jax.numpy.asarray(windows[: config["reference"]["sample"]])
        rows.append({"seed": seed, **report(params, tokens)})
        del params
        print(json.dumps(rows[-1]), flush=True)
    summary = {
        "device": {"platform": device.platform, "kind": device.device_kind},
        "cell": args.cell, "tiny": args.tiny, "seeds": rows,
        "min_margin": min(r["min_margin"] for r in rows),
        "max_score_diff": max(r["max_score_diff"] for r in rows),
        "flipped_tokens": sum(r["flipped_tokens"] for r in rows),
        "flipped_tokens_held_here": sum(
            r["flipped_tokens_held_here"] for r in rows),
        "near_ties": {str(m): [sum(r["near_ties"][str(m)][i] for r in rows)
                               for i in (0, 1)] for m in NEAR},
    }
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(summary, indent=1))
    print(json.dumps({k: v for k, v in summary.items() if k != "seeds"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
