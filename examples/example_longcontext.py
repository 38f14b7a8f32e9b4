#!/usr/bin/env python
"""Long-context tour: ring attention over a sequence-sharded mesh.

New capability beyond the reference (it has no attention at all -
SURVEY.md checklist; long context is this framework's first-class
extension).  A sequence of length T shards into T/n chunks over the
``sp`` axis; each device holds its chunk's queries while K/V blocks
rotate around the ring (``lax.ppermute``), folding into a running
online-softmax - O(T/n) activation memory per device instead of O(T^2)
scores, which is what makes million-token contexts reachable on a real
slice.  This example:

1. runs ring attention on an 8-way sp mesh and checks it against plain
   full-sequence attention - exact to float tolerance;
2. does the same through Ulysses (all_to_all head-scatter) - the other
   sequence-parallel layout, better when heads >> devices;
3. runs the causal variant (the LM case: each position attends to its
   prefix ONLY, across chunk boundaries - a traced per-shard offset
   drives the mask);
4. trains one step of the attention classifier over the composed
   dp x sp x tp mesh to show the ring inside a real training program.

Demos 1-3 use the dense XLA online-softmax inner directly (the numerics
reference); demo 4 resolves the model's attention impl like the CLI
does, which on a TPU selects the fused Pallas flash kernel as the
per-shard inner (``ops/pallas_attention.py``) - the numerics contract
is identical either way.

Run on an 8-way virtual CPU mesh:
  PDRNN_PLATFORM=cpu PDRNN_NUM_CPU_DEVICES=8 \
      python examples/example_longcontext.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from pytorch_distributed_rnn_tpu.utils import apply_platform_overrides

apply_platform_overrides()

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from pytorch_distributed_rnn_tpu.models import AttentionClassifier
from pytorch_distributed_rnn_tpu.ops.attention import (
    mha_attention,
    ring_attention,
    ulysses_attention,
)
from pytorch_distributed_rnn_tpu.parallel import make_mesh
from pytorch_distributed_rnn_tpu.parallel.combined import make_3d_train_step

SP = 8
B, H, T, D = 2, 8, 256, 32  # T shards into 8 chunks of 32


def main():
    if len(jax.devices()) < SP:
        raise SystemExit(
            f"needs {SP} devices (set PDRNN_PLATFORM=cpu "
            f"PDRNN_NUM_CPU_DEVICES={SP})"
        )
    mesh = make_mesh({"sp": SP})
    rng = np.random.RandomState(0)
    q, k, v = (
        jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
        for _ in range(3)
    )

    # 1. ring attention == full attention (time sharded over sp)
    @partial(shard_map, mesh=mesh, in_specs=(P(None, None, "sp"),) * 3,
             out_specs=P(None, None, "sp"), check_vma=False)
    def ring(q, k, v):
        return ring_attention(q, k, v, "sp")

    out_ring = jax.jit(ring)(q, k, v)
    out_full = mha_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_full),
                               rtol=2e-5, atol=2e-5)
    print(f"ring == full attention over sp={SP}: "
          f"max|diff| = {float(jnp.abs(out_ring - out_full).max()):.2e}")

    # 2. Ulysses (all_to_all head scatter) == full attention
    @partial(shard_map, mesh=mesh, in_specs=(P(None, None, "sp"),) * 3,
             out_specs=P(None, None, "sp"), check_vma=False)
    def ulysses(q, k, v):
        return ulysses_attention(q, k, v, "sp")

    out_u = jax.jit(ulysses)(q, k, v)
    np.testing.assert_allclose(np.asarray(out_u), np.asarray(out_full),
                               rtol=2e-5, atol=2e-5)
    print(f"ulysses == full attention over sp={SP}: "
          f"max|diff| = {float(jnp.abs(out_u - out_full).max()):.2e}")

    # 3. causal ring: each position attends to its global prefix only -
    # chunk boundaries included (the per-shard offset is traced)
    @partial(shard_map, mesh=mesh, in_specs=(P(None, None, "sp"),) * 3,
             out_specs=P(None, None, "sp"), check_vma=False)
    def ring_causal(q, k, v):
        return ring_attention(q, k, v, "sp", causal=True)

    out_rc = jax.jit(ring_causal)(q, k, v)
    out_fc = mha_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out_rc), np.asarray(out_fc),
                               rtol=2e-5, atol=2e-5)
    print(f"causal ring == causal full over sp={SP}: "
          f"max|diff| = {float(jnp.abs(out_rc - out_fc).max()):.2e}")

    # 4. the CAUSAL ring inside a real training step: dp x sp x tp with
    # causal=True - the LM framing of demo 3 threaded through the whole
    # composed program (the plain non-causal composition is demo 1 of
    # examples/example_4d.py; this one is the long-context variant)
    axes = {"dp": 2, "sp": 2, "tp": 2}
    mesh3d = make_mesh(axes)
    model = AttentionClassifier(input_dim=9, dim=32, depth=2, num_heads=4,
                                output_dim=6, max_len=64)
    params = model.init(jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    state = opt.init(params)
    step = make_3d_train_step(model, opt, mesh3d, causal=True,
                              donate=False)
    x = jnp.asarray(rng.randn(4, 64, 9).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 6, size=4))
    losses = []
    for _ in range(5):
        params, state, loss = step(params, state, (x, y))
        losses.append(float(loss))
    assert losses[-1] < losses[0], "training did not reduce loss"
    print(f"causal dp x sp x tp training {axes}: "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    print("long-context example OK")


if __name__ == "__main__":
    main()
