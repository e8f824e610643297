"""Extended coverage: MoE equivalences, device-engine properties,
grad-compression collective, serving splice correctness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import poc
from repro.core import DeviceEngine, EventRegistry, Simulator, emits_events


# ---------------------------------------------------------------------------
# MoE: grouped-capacity vs dense-combine equivalence when nothing drops
# ---------------------------------------------------------------------------

def test_moe_grouped_matches_dense_when_dropless():
    from repro.models.moe import moe_apply, moe_apply_dense, moe_init

    E, K, D, F = 4, 2, 32, 16
    key = jax.random.PRNGKey(0)
    params = moe_init(key, d_model=D, d_ff_expert=F, num_experts=E)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, D),
                          jnp.float32).astype(jnp.bfloat16)
    # capacity_factor = E/K => capacity == tokens => no drops
    y_cap, aux = moe_apply(params, x, num_experts=E, top_k=K,
                           capacity_factor=float(E) / K, group_size=16)
    y_dense = moe_apply_dense(params, x, num_experts=E, top_k=K)
    np.testing.assert_allclose(
        np.asarray(y_cap, np.float32), np.asarray(y_dense, np.float32),
        rtol=0.06, atol=0.06)
    assert jnp.isfinite(aux)


def test_moe_group_size_invariance_when_dropless():
    from repro.models.moe import moe_apply, moe_init

    E, K, D, F = 4, 2, 16, 8
    params = moe_init(jax.random.PRNGKey(0), d_model=D, d_ff_expert=F,
                      num_experts=E)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, D)).astype(
        jnp.bfloat16)
    outs = [
        moe_apply(params, x, num_experts=E, top_k=K,
                  capacity_factor=float(E) / K, group_size=g)[0]
        for g in (8, 16, 32, 64)
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0], np.float32),
                                   np.asarray(o, np.float32),
                                   rtol=0.05, atol=0.05)


def test_moe_capacity_drops_are_bounded():
    """With tight capacity, output norm shrinks but stays finite."""
    from repro.models.moe import moe_apply, moe_init

    E, K, D, F = 4, 2, 16, 8
    params = moe_init(jax.random.PRNGKey(0), d_model=D, d_ff_expert=F,
                      num_experts=E)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, D)).astype(
        jnp.bfloat16)
    y, aux = moe_apply(params, x, num_experts=E, top_k=K,
                       capacity_factor=0.5, group_size=64)
    assert bool(jnp.all(jnp.isfinite(y.astype(jnp.float32))))
    assert float(aux) > 0


# ---------------------------------------------------------------------------
# Device engine properties
# ---------------------------------------------------------------------------

@given(
    p_set=st.floats(0.0, 1.0),
    n=st.integers(1, 4),
    num_events=st.integers(1, 24),
)
@settings(max_examples=10, deadline=None)
def test_device_engine_matches_host_property(p_set, n, num_events):
    rng = np.random.default_rng(int(p_set * 100) + n)
    types = [int(x) for x in (rng.random(num_events) < p_set)]
    reg = poc.build_registry(iters=40)
    sim = Simulator(reg, max_batch_len=n)
    for t, ty in enumerate(types):
        sim.queue.push(float(t), ty)
    s_host, _ = sim.run(poc.initial_state(), mode="conservative")

    reg2 = poc.build_registry(iters=40)
    eng = DeviceEngine(reg2, max_batch_len=n, capacity=num_events + 4)
    q = eng.initial_queue([(float(t), ty, None)
                           for t, ty in enumerate(types)])
    s_dev, _, stats = eng.run(poc.initial_state(), q)
    assert int(s_host) == int(s_dev)
    assert int(stats["events"]) == num_events


def test_device_engine_t_end():
    reg = EventRegistry()
    reg.register("A", lambda s, t, a: s + 1, lookahead=0.5)
    eng = DeviceEngine(reg, max_batch_len=2, capacity=16, t_end=3.5)
    q = eng.initial_queue([(float(t), 0, None) for t in range(10)])
    s, _, stats = eng.run(jnp.int32(0), q)
    # events at t=0..3 processed; window closes after t_end
    assert int(s) >= 4


def test_device_queue_fifo_ties():
    """Events with identical timestamps run in insertion order."""
    from repro.core.queue import (device_queue_init, device_queue_pop,
                                  device_queue_push)

    q = device_queue_init(8)
    for i in range(4):
        q = device_queue_push(q, 1.0, i, jnp.zeros((4,)))
    order = []
    for _ in range(4):
        q, t, ty, _ = device_queue_pop(q)
        order.append(int(ty))
    assert order == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# Gradient compression inside shard_map (the real collective path)
# ---------------------------------------------------------------------------

def test_compressed_psum_under_shard_map():
    from jax.sharding import PartitionSpec as P

    from repro.training.compression import compressed_psum_gradients

    mesh = jax.make_mesh((1,), ("data",))
    grads = {"w": jnp.arange(8, dtype=jnp.float32) / 7.0}

    def f(g):
        return compressed_psum_gradients(g, mesh, ("data",))

    out = jax.shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P())(grads)
    err = jnp.abs(out["w"] - grads["w"])
    assert float(err.max()) < 1e-2  # int8 quantization error bound


# ---------------------------------------------------------------------------
# Serving cache splice
# ---------------------------------------------------------------------------

def test_serving_prefill_splice_isolates_slots():
    """Prefilling slot 1 must not perturb slot 0's cache."""
    from repro.configs import get_config
    from repro.models import LM
    from repro.serving.engine import ServingEngine

    cfg = get_config("stablelm-12b").reduced()
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, max_slots=2, max_len=64,
                        max_batch_len=2)
    eng.submit(0, [1, 2, 3], 4, at=0.0)
    eng.waiting.append(eng.requests[0])
    eng._h_prefill(None, 0.0, None)
    snap = jax.tree.map(lambda x: np.asarray(x).copy(),
                        eng.cache["stages"])
    eng.submit(1, [4, 5], 4, at=0.0)
    eng.waiting.append(eng.requests[1])
    eng._h_prefill(None, 0.0, None)

    def check(before, after):
        if before.ndim >= 2:  # [L, B, ...]: slot 0 rows must be equal
            np.testing.assert_array_equal(before[:, 0],
                                          np.asarray(after)[:, 0])

    jax.tree.map(check, snap, eng.cache["stages"])


# ---------------------------------------------------------------------------
# vocab padding
# ---------------------------------------------------------------------------

def test_padded_vocab_logits_masked():
    from repro.configs import get_config
    from repro.models import LM
    import dataclasses

    cfg = dataclasses.replace(
        get_config("granite-moe-1b-a400m").reduced(), vocab_size=250)
    assert cfg.padded_vocab == 256
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 8), jnp.int32)
    logits, _ = model.forward(params, tokens=tokens)
    assert logits.shape[-1] == 256
    # padded ids can never win an argmax
    assert bool(jnp.all(logits[..., 250:] < -1e29))
