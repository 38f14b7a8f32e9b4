"""CLI entrypoint: global flags + required strategy subcommand.

Capability parity with ``/root/reference/src/motion/main.py:15-43`` - same
flag surface and defaults, same dispatch shape (``args.func(args)``).
Subcommands: ``local``, ``distributed``, ``horovod``,
``parameter-server``.

Consciously fixed vs the reference (see PARITY.md): ``--validation-fraction``
is actually forwarded to the dataset split (the reference parses it but the
processor default silently governs); ``--seed`` seeds model init and the
sampler (there is no global mutable RNG in JAX to seed); ``--dropout`` is
REAL train-mode inter-layer dropout threaded through the models (the
reference parsed it but never used it, ``main.py:26``).  New flags:
``--cell {lstm,gru}`` and ``--resume PATH`` (checkpoint resume; reference
checkpoints were write-only).  ``--num-threads`` is accepted for CLI
compatibility only.

Run:
  python -m pytorch_distributed_rnn_tpu.main --epochs 2 --seed 123456789 local
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

from pytorch_distributed_rnn_tpu.utils import apply_platform_overrides

DEFAULT_CHECKPOINT_DIR = Path("models")
DEFAULT_DATASET_PATH = Path("data")


def build_parser() -> argparse.ArgumentParser:
    # imported here, not at the top, so that importing this module stays
    # cheap (the registries import JAX)
    from pytorch_distributed_rnn_tpu import param_server, training
    from pytorch_distributed_rnn_tpu.training import families

    parser = argparse.ArgumentParser(
        description="TPU-native distributed RNN trainer"
    )
    parser.add_argument(
        "--checkpoint-directory", default=DEFAULT_CHECKPOINT_DIR, type=Path
    )
    parser.add_argument("--dataset-path", default=DEFAULT_DATASET_PATH, type=Path)
    parser.add_argument("--output-path", default=None, type=Path)
    parser.add_argument("--stacked-layer", default=2, type=int)
    parser.add_argument("--hidden-units", default=32, type=int)
    parser.add_argument("--epochs", default=100, type=int)
    parser.add_argument("--validation-fraction", default=0.1, type=float)
    parser.add_argument("--batch-size", default=1440, type=int)
    parser.add_argument("--learning-rate", default=0.0025, type=float)
    parser.add_argument("--dropout", default=0.1, type=float)
    parser.add_argument("--log", default="INFO")
    parser.add_argument("--num-threads", default=4, type=int)
    parser.add_argument("--seed", default=None, type=int)
    parser.add_argument("--no-validation", action="store_true")
    parser.add_argument("--cell", default="lstm", choices=["lstm", "gru"])
    # --model, and each family's own flags, come from the model classes
    families.add_model_flags(parser)
    parser.add_argument(
        "--seq-length", default=None, type=int, metavar="T",
        help="token-window length for --model char / mla_moe / "
        "hybrid_ssm_moe (default 128); motion/attention take their length "
        "from the HAR data",
    )
    parser.add_argument(
        "--vocab-size", default=None, type=int, metavar="V",
        help="vocabulary of the token families (--model char / mla_moe / "
        "hybrid_ssm_moe): rows of the embedding and the output head.  Default: what the "
        "data declares (256 for a byte corpus).  A slice of a larger "
        "vocabulary is a smaller vocabulary: the data's ids must lie "
        "under it",
    )
    parser.add_argument(
        "--num-heads", default=4, type=int,
        help="attention heads (--model attention; must divide "
        "--hidden-units)",
    )
    parser.add_argument(
        "--num-experts", default=4, type=int,
        help="expert count for --model moe (must shard over the ep mesh "
        "axis); expert FFN hidden dim defaults to 2 x --hidden-units",
    )
    parser.add_argument(
        "--moe-top-k", default=1, type=int,
        help="experts per token.  --model mla_moe / hybrid_ssm_moe: any "
        "number up to --num-experts (the published 8 and 6: pass it).  "
        "--model moe, 1 or 2 "
        "only: 1 = Switch routing "
        "(raw max-gate combine weight), 2 = GShard (renormalized top-2 "
        "gates; capacity slots assigned choice-major so second choices "
        "drop first under pressure)",
    )
    parser.add_argument(
        "--moe-router", default="token", choices=["token", "expert"],
        help="--model moe routing direction: token (tokens pick experts "
        "- Switch/GShard, see --moe-top-k) or expert (expert-choice: "
        "each expert picks its top-C tokens - perfectly balanced by "
        "construction, no aux loss)",
    )
    parser.add_argument(
        "--moe-capacity-factor", default=2.0, type=float, metavar="F",
        help="per-expert slot budget for --model moe: capacity = "
        "ceil(tokens x selections x F / experts).  Applies to the "
        "dispatched paths: the ep mesh strategy (token-choice drops "
        "overflow past it, residual passes through) and expert-choice "
        "routing on every strategy (each expert fills exactly this many "
        "slots).  Token-choice on the non-mesh strategies runs the "
        "dense-exact path, which computes every expert and drops "
        "nothing - the flag has no effect there",
    )
    parser.add_argument(
        "--moe-group-size", default=None, type=int, metavar="G",
        help="token-choice --model moe on the ep mesh strategy: route "
        "each shard's tokens in independent groups of G (GShard grouped "
        "routing) - capacity becomes per-group, keeping the one-hot "
        "dispatch einsums linear in token count.  Default: one global "
        "group per shard (exact-union drop semantics)",
    )
    # the flags that both decoder LMs (--model mla_moe / hybrid_ssm_moe) read
    parser.add_argument(
        "--ffn-dims", default=None, metavar="A,EXPERT",
        help="widths of the decoder LMs' feed-forward parts: --model "
        "mla_moe DENSE,EXPERT, the leading dense layer's MLP and one "
        "expert (intermediate_size, moe_intermediate_size; default "
        "7168,768); --model hybrid_ssm_moe SHARED,EXPERT, the shared "
        "expert and one routed expert (moe_shared_expert_intermediate_size, "
        "moe_intermediate_size; default 3712,1856)",
    )
    parser.add_argument(
        "--experts-held", default=None, metavar="FIRST:COUNT",
        help="--model mla_moe / hybrid_ssm_moe: the share of each layer's "
        "--num-experts routed experts this chip holds, as an "
        "expert-parallel rank does.  The router scores all experts; the "
        "layer computes its own experts' part for the tokens routed to "
        "them and drops none.  Default: all of them",
    )
    parser.add_argument(
        "--moe-route-scale", default=2.5, type=float,
        help="--model mla_moe / hybrid_ssm_moe: routed_scaling_factor on "
        "the normalised weights of the picked experts",
    )
    parser.add_argument(
        "--moe-route-eps", default=0.0, type=float,
        help="--model mla_moe / hybrid_ssm_moe: what the family adds to "
        "the sum of the picked experts' scores before it divides by it "
        "(lfm2_moe: 1e-6; the default adds nothing)",
    )
    parser.add_argument(
        "--rope-theta", default=None, type=float,
        help="base of the rotary embedding (rope_theta).  --model "
        "mla_moe: over interleaved pairs of the rotary part of q / k "
        "(default 32e6).  --model hybrid_ssm_moe: over the whole head of "
        "q and k, pairs (i, i + head / 2) (lfm2_moe: 1e6); default none, "
        "attention without position",
    )
    parser.add_argument(
        "--resume", default=None, type=Path, metavar="PATH|auto",
        help="restore params/optimizer state before training.  A path "
        "loads that checkpoint and retrains the full --epochs on top of "
        "it (historical behavior); the literal 'auto' finds the newest "
        "VALID checkpoint under --checkpoint-directory (corrupt/"
        "truncated files are skipped - resilience/guard.py), CONTINUES "
        "from its epoch, and starts fresh when none exists - the "
        "crash-restart contract",
    )
    parser.add_argument(
        "--checkpoint-every", default=0, type=int, metavar="N",
        help="also write checkpoint-epoch-N.ckpt every N epochs "
        "(0 = best-model-only, the reference's trigger)",
    )
    parser.add_argument(
        "--keep-checkpoints", default=0, type=int, metavar="N",
        help="rotate periodic epoch checkpoints, keeping only the newest "
        "N (0 = keep all; best-model.ckpt is never rotated)",
    )
    parser.add_argument(
        "--max-bad-steps", default=0, type=int, metavar="K",
        help="non-finite guard: skip (not apply) any update step whose "
        "gradients contain NaN/Inf, count it, and abort only after K "
        "consecutive bad steps; 0 disables the guard (historical "
        "behavior: a NaN poisons the params)",
    )
    parser.add_argument(
        "--faults", default=None, metavar="SPEC",
        help="deterministic chaos schedule (resilience/faults.py), e.g. "
        "'step:3:nan,step:7:stall:0.5,epoch:2:kill,net:delay:100,"
        "seed:7'; also read from the PDRNN_CHAOS env when the flag is "
        "absent.  net:* events bridge onto the transport's "
        "PDRNN_FAULT_* contract (the bench netem analogue)",
    )
    parser.add_argument(
        "--grad-accum", default=1, type=int, metavar="K",
        help="accumulate gradients over K equal microbatches per optimizer "
        "step (local trainer; batch sizes must divide by K) - the "
        "activation-memory lever for batches that do not fit HBM",
    )
    parser.add_argument(
        "--sharded-update", default=True,
        action=argparse.BooleanOptionalAction,
        help="cross-replica sharded weight update (2004.13336) on the "
        "pure data-parallel strategies (distributed / horovod / "
        "distributed-native): reduce-scatter the gradient, apply a "
        "1/world-sharded optimizer update, allgather fresh params - "
        "~2x less update-phase collective bytes and 1/world the "
        "optimizer-state memory, bitwise-identical results.  Default "
        "on; --no-sharded-update restores the replicated full apply.  "
        "Inert on strategies that already shard the update (fsdp/mesh)",
    )
    parser.add_argument(
        "--bucketed-comm", default=True,
        action=argparse.BooleanOptionalAction,
        help="overlap gradient communication with the sharded optimizer "
        "apply on distributed-native: the flat gradient is split into "
        "--bucket-mb buckets whose reduce-scatters/allgathers stream on "
        "a comm worker thread while the host applies already-landed "
        "buckets - bitwise-identical to the monolithic schedule, same "
        "wire bytes.  Default on; --no-bucketed-comm restores the "
        "monolithic blocking collectives (the escape hatch if a "
        "transport misbehaves under concurrent handles).  Requires "
        "--sharded-update; inert elsewhere",
    )
    parser.add_argument(
        "--bucket-mb", default=25.0, type=float, metavar="MB",
        help="gradient bucket size in MiB of total wire traffic per "
        "bucket (default 25, torch DDP's bucket_cap_mb); smaller "
        "buckets start overlap earlier but pay more per-collective "
        "latency - tune down for slow links, up for tiny models",
    )
    parser.add_argument(
        "--precision", default="f32", choices=["f32", "bf16"],
        help="bf16: bfloat16 compute (full MXU rate, half the HBM "
        "traffic) with f32 parameters and optimizer state",
    )
    parser.add_argument(
        "--remat", action="store_true",
        help="recompute RNN activations during backward instead of "
        "saving them (trades FLOPs for HBM; for deep/long configs)",
    )
    parser.add_argument(
        "--checkpoint-format", default="gathered",
        choices=["gathered", "sharded"],
        help="gathered: reference-parity single file (state gathered to "
        "the writing host).  sharded: orbax per-shard writes - each "
        "process/device writes only the shards it owns, restore places "
        "them back without ever building a host-side replica (the scale "
        "path for fsdp/mesh layouts); --resume accepts the resulting "
        ".orbax directory",
    )
    parser.add_argument(
        "--checkpoint-async", action="store_true",
        help="hand sharded checkpoint writes to orbax's background "
        "thread so serialization overlaps training (drained before the "
        "next save and at train end); needs --checkpoint-format sharded",
    )
    parser.add_argument(
        "--fuse-run", action="store_true",
        help="compile the whole multi-epoch training run into ONE device "
        "program (lax.scan over epochs) even with INFO logging on; "
        "removes every per-epoch host round-trip at the cost of "
        "per-epoch Start-Epoch messages.  Needs --no-validation, no --checkpoint-every and "
        "--grad-accum 1; rejected loudly otherwise",
    )
    parser.add_argument(
        "--profile", default=None, type=Path, metavar="DIR",
        help="capture a step-level device trace of the training run into "
        "DIR (viewable in TensorBoard/Perfetto); the reference had only "
        "whole-run wall-clock + RSS",
    )
    parser.add_argument(
        "--profile-steps", default=None, metavar="A:B",
        help="bound the --profile capture to optimizer steps [A, B) "
        "instead of tracing the whole run (steady-state steps without "
        "the compile/warm-up noise); a profiler that cannot start is "
        "skipped with a warning on the CPU and an error on an accelerator",
    )
    parser.add_argument(
        "--metrics", default=None, type=Path, metavar="PATH",
        help="structured run telemetry (obs/): write rank-tagged JSONL "
        "events (per-step loss/timing/data-wait, collective traffic, "
        "memory peaks, checkpoint/chaos/guard events) to PATH, buffered "
        "off the hot path; summarize with pdrnn-metrics.  Also read "
        "from the PDRNN_METRICS env when the flag is absent.  The "
        "legacy perf line is emitted either way",
    )
    parser.add_argument(
        "--metrics-sample-every", default=None, type=int, metavar="N",
        help="telemetry fence cadence: every N-th step blocks on the "
        "step's outputs to measure true step wall time (default 16); "
        "the other steps stay fully async",
    )
    parser.add_argument(
        "--live", default=None, metavar="[HOST:]PORT",
        help="live observability plane (obs/live.py; needs --metrics): "
        "rank 0 serves GET /metrics (Prometheus text), /health "
        "(ok/stalled/dead/drained per rank), /events (recent alerts) "
        "and /fleet on this address; other ranks push digests to it.  "
        "Arms the anomaly watchdog (in-run stall detection with "
        "all-thread stack dumps, NaN streaks, loss spikes; tune via "
        "PDRNN_WATCHDOG_STALL seconds, disable with PDRNN_WATCHDOG=0).  "
        "Also read from the PDRNN_LIVE env when the flag is absent.  "
        "Watch it live with `pdrnn-metrics watch HOST:PORT`",
    )
    parser.add_argument(
        "--live-port-file", default=None, type=Path, metavar="PATH",
        help="write 'host port' of the live endpoint here once bound "
        "(how scripts and tests find a --live 0 ephemeral port)",
    )

    sub_parser = parser.add_subparsers(
        title="Available commands", metavar="command [options ...]"
    )
    sub_parser.required = True

    param_server.add_sub_command(sub_parser)
    training.add_sub_commands(sub_parser)
    return parser


def main(argv=None):
    apply_platform_overrides()
    # parse first (no JAX computation happens there) so --help and bad
    # command lines fail fast instead of blocking on a rendezvous
    args = build_parser().parse_args(argv)
    from pytorch_distributed_rnn_tpu.utils import leakcheck

    # resolve PDRNN_LEAKCHECK before the first socket/thread/file
    leakcheck.maybe_install()
    # env-gated multi-host rendezvous (PDRNN_COORDINATOR, or MASTER_ADDR
    # under PDRNN_MULTIHOST=1): must run before the first JAX computation;
    # no-op single-controller otherwise.  The mpirun analogue - SURVEY.md §5.
    from pytorch_distributed_rnn_tpu.parallel.multihost import (
        initialize_multihost,
    )

    initialize_multihost()
    return args.func(args)


if __name__ == "__main__":
    sys.exit(0 if main() is not None else 1)
