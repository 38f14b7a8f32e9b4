"""HLO collective-traffic report: the communication side of the scaling
model, measured from the COMPILED programs instead of wall-clock.

One chip (or a virtual CPU mesh) cannot measure scaling wall-clock - 8
virtual devices share the same host cores, so a "scaling study" there has
no scaling signal.  What the compiled program DOES pin
down exactly, on any backend, is how many bytes each training step moves
through each collective: XLA's post-optimization HLO carries every
``all-reduce`` / ``all-gather`` / ``reduce-scatter`` /
``collective-permute`` / ``all-to-all`` with concrete shapes.  Those bytes
plus a link bandwidth ARE the communication term of the scaling model (the
"How to Scale Your Model" recipe: count bytes, divide by ICI/DCN
bandwidth, compare with compute time).

``collective_stats`` parses a compiled module's text; ``report_programs``
compiles the framework's flagship SPMD programs on a virtual mesh and
returns one stats row per program.
"""

from __future__ import annotations

import re

# bytes per element for the dtypes XLA prints in shape strings
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)

# `f32[8,128]{1,0} all-reduce(` and tuple-shaped variants
_OP_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|\S+)\s+"
    r"(?P<op>" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"(?P<dtype>[a-z]+\d*)\[(?P<dims>[\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of one HLO shape string (tuples summed)."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dtype = m.group("dtype")
        width = _DTYPE_BYTES.get(dtype)
        if width is None:
            continue  # token[] and friends carry no data
        dims = m.group("dims")
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * width
    return total


def collective_stats(hlo_text: str) -> dict:
    """{op_kind: {"count": N, "bytes": output bytes per step}} over a
    compiled module's text.  ``-start``/``-done`` async pairs count once,
    via the ``-done`` side: a ``-start`` result tuple bundles operand
    aliases WITH the result buffers, so summing it would double-count the
    transfer, while the ``-done`` result is exactly the transferred
    data.

    CAVEAT: text parsing sees each op ONCE even when it sits inside a
    ``while`` body (a ``lax.scan`` - e.g. the sp relay's per-turn
    ppermute), so loop-executed collectives are understated by the trip
    count.  :func:`trace_collective_stats` counts from the jaxpr, where
    scan lengths are static - use that for per-step traffic totals."""
    stats: dict = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m or "-start(" in line:
            continue
        op = m.group("op")
        entry = stats.setdefault(op, {"count": 0, "bytes": 0})
        entry["count"] += 1
        entry["bytes"] += _shape_bytes(m.group("shape"))
    return stats


def compiled_text(fn, *args) -> str:
    import jax

    return jax.jit(fn).lower(*args).compile().as_text()


# jax collective primitives -> the HLO op names the rest of the report uses
_COLLECTIVE_PRIMS = {
    "psum": "all-reduce",
    "pmax": "all-reduce",
    "pmin": "all-reduce",
    "ppermute": "collective-permute",
    "all_to_all": "all-to-all",
    "all_gather": "all-gather",
    # jax.lax.psum_scatter traces as the reduce_scatter primitive
    "reduce_scatter": "reduce-scatter",
}


def trace_collective_stats(fn, *args) -> dict:
    """Per-step collective traffic counted from the JAXPR (trace only, no
    compile): every collective primitive's result bytes, with enclosing
    ``lax.scan`` trip counts multiplied in - the count HLO text parsing
    gets wrong for loop-executed collectives (the sp relay's per-turn
    ppermute compiles to ONE collective-permute inside a ``while`` body
    but executes ``sp`` times per step).  Gradient collectives are
    included when ``fn`` contains the grad (trace the full train step).

    Bytes are per-device result sizes (the same convention as the HLO
    parse).  XLA may later merge small same-operand collectives, so the
    compiled COUNT can be lower; the traced BYTES are the semantic
    per-step traffic the scaling model needs.
    """
    import jax

    return closed_jaxpr_collective_stats(jax.make_jaxpr(fn)(*args))


def closed_jaxpr_collective_stats(closed) -> dict:
    """:func:`trace_collective_stats` on an already-made ClosedJaxpr -
    shared with the lint deep pass (``lint/jaxpr_pass.py``), which has
    the traced step in hand and reports per-entry collective traffic in
    its CI artifact."""
    import numpy as np

    jaxpr_cls = type(closed.jaxpr)
    closed_cls = type(closed)
    stats: dict = {}

    def add(op, count, nbytes):
        entry = stats.setdefault(op, {"count": 0, "bytes": 0})
        entry["count"] += count
        entry["bytes"] += nbytes

    def aval_bytes(var):
        aval = getattr(var, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            return 0
        if not hasattr(aval, "dtype"):
            return 0
        n = int(np.prod(aval.shape, dtype=np.int64)) if aval.shape else 1
        return n * aval.dtype.itemsize

    def subjaxprs(params):
        found = []

        def maybe(x):
            if isinstance(x, closed_cls):
                found.append(x.jaxpr)
            elif isinstance(x, jaxpr_cls):
                found.append(x)

        for value in params.values():
            maybe(value)
            if isinstance(value, (tuple, list)):
                for item in value:
                    maybe(item)
        return found

    def visit(jaxpr, mult):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in _COLLECTIVE_PRIMS:
                nbytes = sum(aval_bytes(v) for v in eqn.outvars)
                add(_COLLECTIVE_PRIMS[name], mult, nbytes * mult)
            sub_mult = mult
            if name == "scan":
                sub_mult = mult * int(eqn.params.get("length", 1))
            elif name == "while":
                # dynamic trip count: cannot be known from the trace -
                # count once and surface the uncertainty
                add("while-body(unknown-trip-count)", 1, 0)
            for sub in subjaxprs(eqn.params):
                visit(sub, sub_mult)

    visit(closed.jaxpr, 1)
    if stats.get("while-body(unknown-trip-count)", {}).get("count") == 0:
        stats.pop("while-body(unknown-trip-count)", None)
    return stats


def _motion_dp_program(n: int):
    """Data-parallel motion step on a dp=n mesh (the DDP strategy's
    gradient psum -> XLA AllReduce)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_rnn_tpu.models import MotionModel
    from pytorch_distributed_rnn_tpu.ops import cross_entropy_loss
    from pytorch_distributed_rnn_tpu.parallel import (
        make_mesh,
        make_spmd_train_step,
    )

    mesh = make_mesh({"dp": n})
    model = MotionModel(input_dim=9, hidden_dim=32, layer_dim=2,
                        output_dim=6, impl="scan")
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.adam(2.5e-3)
    opt_state = opt.init(params)

    def loss_and_metrics(p, batch):
        x, y = batch
        logits = model.apply(p, x)
        return cross_entropy_loss(logits, y), {
            "correct": jnp.sum(jnp.argmax(logits, axis=1) == y)
        }

    step = make_spmd_train_step(loss_and_metrics, opt, mesh, donate=False)
    rng = np.random.RandomState(0)
    batch = (
        jnp.asarray(rng.randn(2 * n, 16, 9).astype(np.float32)),
        jnp.asarray(rng.randint(0, 6, size=2 * n)),
    )
    return step, (params, opt_state, batch), params


def _fsdp_program(n: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_rnn_tpu.models import CharRNN
    from pytorch_distributed_rnn_tpu.parallel import make_mesh
    from pytorch_distributed_rnn_tpu.parallel.zero import (
        init_sharded,
        init_sharded_opt_state,
        make_fsdp_train_step,
    )

    mesh = make_mesh({"dp": n})
    lm = CharRNN(vocab_size=32, embed_dim=16, hidden_dim=16 * n,
                 layer_dim=1, impl="scan")
    params, shard = init_sharded(lm, jax.random.PRNGKey(3), mesh)
    opt = optax.adam(1e-3)
    state, oshard = init_sharded_opt_state(opt, params, mesh)
    step = make_fsdp_train_step(lm.loss, opt, mesh, shard, oshard,
                                donate=False)
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, 32, size=(n, 8)), jnp.int32)
    return step, (params, state, tok), params


def _char_sp_program(dp: int, sp: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_rnn_tpu.models import CharRNN
    from pytorch_distributed_rnn_tpu.parallel import make_mesh
    from pytorch_distributed_rnn_tpu.parallel.strategy import (
        make_char_mesh_loss_fn,
        make_mesh_grad_step,
    )

    axes = {"dp": dp, "sp": sp}
    mesh = make_mesh(axes)
    lm = CharRNN(vocab_size=32, embed_dim=8, hidden_dim=8, layer_dim=2,
                 impl="scan")
    params = lm.init(jax.random.PRNGKey(4))
    opt = optax.adam(1e-3)
    state = opt.init(params)
    loss_fn = make_char_mesh_loss_fn(mesh, axes)
    step = make_mesh_grad_step(loss_fn, opt)
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, 32, size=(2 * dp, 16)), jnp.int32)
    batch = (toks, jnp.zeros(2 * dp, jnp.int32))
    return jax.jit(step), (params, state, batch), params


def _motion_pp_program(dp: int, pp: int, schedule: str = "gpipe",
                       num_microbatches: int = 2, num_chunks: int = 1):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_rnn_tpu.models import MotionModel
    from pytorch_distributed_rnn_tpu.parallel import make_mesh
    from pytorch_distributed_rnn_tpu.parallel.strategy import (
        make_mesh_grad_step,
        make_motion_mesh_loss_fn,
        make_motion_pp_1f1b_loss_fn,
    )

    axes = {"dp": dp, "pp": pp}
    mesh = make_mesh(axes)
    model = MotionModel(input_dim=9, hidden_dim=8,
                        layer_dim=pp * num_chunks, output_dim=6)
    params = model.init(jax.random.PRNGKey(6))
    opt = optax.adam(1e-3)
    state = opt.init(params)
    if schedule in ("1f1b", "interleaved"):
        loss_fn = make_motion_pp_1f1b_loss_fn(
            mesh, axes, num_microbatches=num_microbatches,
            num_chunks=num_chunks)
    else:
        loss_fn = make_motion_mesh_loss_fn(
            mesh, axes, num_microbatches=num_microbatches)
    step = make_mesh_grad_step(loss_fn, opt)
    rng = np.random.RandomState(0)
    bsz = 2 * num_microbatches * dp
    batch = (
        jnp.asarray(rng.randn(bsz, 16, 9).astype(np.float32)),
        jnp.asarray(rng.randint(0, 6, size=bsz)),
    )
    return jax.jit(step), (params, state, batch), params


def _moe_ep_program(dp: int, ep: int, group_size: int | None = None):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_rnn_tpu.models import MoEClassifier
    from pytorch_distributed_rnn_tpu.parallel import make_mesh
    from pytorch_distributed_rnn_tpu.parallel.strategy import (
        make_mesh_grad_step,
        make_moe_mesh_loss_fn,
    )

    mesh = make_mesh({"dp": dp, "ep": ep})
    model = MoEClassifier(input_dim=9, hidden_dim=16, layer_dim=1,
                          output_dim=6, num_experts=ep * 2,
                          group_size=group_size)
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    state = opt.init(params)
    step = make_mesh_grad_step(make_moe_mesh_loss_fn(model, mesh), opt)
    rng = np.random.RandomState(0)
    batch = (
        jnp.asarray(rng.randn(2 * dp * ep, 12, 9).astype(np.float32)),
        jnp.asarray(rng.randint(0, 6, size=2 * dp * ep)),
    )
    return jax.jit(step), (params, state, batch), params


def param_bytes(params) -> int:
    import jax
    import numpy as np

    return int(sum(
        np.prod(p.shape) * p.dtype.itemsize for p in jax.tree.leaves(params)
    ))


def report_programs(n_devices: int = 8) -> list[dict]:
    """Trace the flagship SPMD programs on an ``n_devices`` virtual mesh
    and report each one's per-step collective traffic (jaxpr-counted, so
    scan-executed collectives carry their trip counts - see
    :func:`trace_collective_stats`)."""
    if n_devices < 4 or n_devices % 4:
        raise ValueError(
            f"collective-report needs a multiple of 4 devices (the sp/ep "
            f"rows factor the mesh as dp x 4), got {n_devices}"
        )
    from pytorch_distributed_rnn_tpu.parallel.pp import pp_schedule_stats

    rows = []
    for name, build, extra in (
        (f"motion dp={n_devices} (DDP grad psum)",
         lambda: _motion_dp_program(n_devices), None),
        (f"char fsdp dp={n_devices} (ZeRO gather/scatter)",
         lambda: _fsdp_program(n_devices), None),
        (f"char mesh dp={n_devices // 4},sp=4 (relay ppermute)",
         lambda: _char_sp_program(n_devices // 4, 4), None),
        (f"moe mesh dp={n_devices // 4},ep=4 (all_to_all dispatch)",
         lambda: _moe_ep_program(n_devices // 4, 4), None),
        # grouped routing: per-shard 24 tokens in four groups of 6 - the
        # all_to_all slot dim grows to groups x per-group-capacity (the
        # padded-slot wire-bytes trade the ep docstring documents) while
        # dispatch compute shrinks; this row makes the trade measurable
        (f"moe mesh dp={n_devices // 4},ep=4 (grouped routing, G=6)",
         lambda: _moe_ep_program(n_devices // 4, 4, group_size=6), None),
        (f"motion mesh dp={n_devices // 2},pp=2 (GPipe stage ppermute)",
         lambda: _motion_pp_program(n_devices // 2, 2),
         {"schedule": [pp_schedule_stats(2, m, "gpipe")
                       for m in (2, 4, 8)]}),
        (f"motion mesh dp={n_devices // 2},pp=2 (1F1B self-scheduled)",
         lambda: _motion_pp_program(n_devices // 2, 2, schedule="1f1b"),
         {"schedule": [pp_schedule_stats(2, m, "1f1b")
                       for m in (2, 4, 8)]}),
        (f"motion mesh dp={n_devices // 2},pp=2 (interleaved, 2 chunks)",
         lambda: _motion_pp_program(n_devices // 2, 2,
                                    schedule="interleaved", num_chunks=2),
         {"schedule": [pp_schedule_stats(2, m, "interleaved",
                                         num_chunks=2)
                       for m in (2, 4, 8)]}),
    ):
        fn, call_args, params = build()
        # Two complementary views, each honest about its blind spot:
        # - traced: jaxpr collectives with scan trip counts multiplied in
        #   (the semantic per-step traffic), but BLIND to GSPMD-inserted
        #   collectives - sharding-annotation programs like the ZeRO step
        #   trace as empty because the compiler inserts their gathers;
        # - compiled: the post-optimization HLO ops (GSPMD included), but
        #   a collective inside a while body (a lax.scan) is counted once
        #   regardless of trip count.
        # Read per-op totals as max(traced, compiled).
        rows.append({
            "program": name,
            "param_bytes": param_bytes(params),
            "traced": trace_collective_stats(fn, *call_args),
            "compiled": collective_stats(compiled_text(fn, *call_args)),
        })
        if extra:
            # pp rows carry the schedule timetable accounting: ticks,
            # busy/idle stage-slots and the bubble fraction per
            # microbatch count (idle shrinks as M grows)
            rows[-1].update(extra)
    return rows
