"""Runtime: compression (error feedback), overlap, fault tolerance."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.runtime import (int8_compress, int8_decompress, DelayedGradSync,
                           FaultInjector, Heartbeat, ResilientRunner)
from repro.runtime.fault_tolerance import StepFailure


# ---------------------------------------------------------------------------
# int8 compression
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.floats(0.01, 100.0))
def test_int8_quant_error_bound(seed, scale):
    g = jax.random.normal(jax.random.PRNGKey(seed), (256,)) * scale
    q, s = int8_compress(g)
    err = jnp.max(jnp.abs(int8_decompress(q, s) - g))
    assert float(err) <= float(s) * 0.5 + 1e-6


def test_int8_zero_tensor():
    q, s = int8_compress(jnp.zeros((16,)))
    assert float(jnp.max(jnp.abs(int8_decompress(q, s)))) == 0.0


def test_error_feedback_unbiased_longrun():
    """With error feedback, the ACCUMULATED applied update converges to the
    accumulated true gradient (residual stays bounded)."""
    key = jax.random.PRNGKey(0)
    err = jnp.zeros((64,))
    applied = jnp.zeros((64,))
    true_sum = jnp.zeros((64,))
    for t in range(200):
        key, k = jax.random.split(key)
        g = jax.random.normal(k, (64,)) * 0.1 + 0.05   # biased stream
        target = g + err
        q, s = int8_compress(target)
        deq = int8_decompress(q, s)
        err = target - deq
        applied = applied + deq
        true_sum = true_sum + g
    # residual == err, bounded by one quantization step
    gap = float(jnp.max(jnp.abs(applied + err - true_sum)))
    assert gap < 1e-4
    assert float(jnp.max(jnp.abs(err))) < 0.05   # residual did not blow up


# ---------------------------------------------------------------------------
# delayed grad sync
# ---------------------------------------------------------------------------

def test_delayed_sync_is_shifted_schedule():
    """Applied gradient at step t == reduced local grad from step t-1."""
    sync = DelayedGradSync(reduce_fn=lambda g: g * 0.5)   # fake reduction
    applied = []

    def local_grads(params, batch):
        return jnp.float32(batch), None

    def apply_update(params, opt, g):
        applied.append(float(g))
        return params - g, opt

    params, opt = jnp.float32(0.0), None
    pending = jnp.float32(0.0)
    batches = [1.0, 2.0, 3.0, 4.0]
    for b in batches:
        params, opt, pending, _ = sync.step(
            params, opt, pending, b, local_grads=local_grads,
            apply_update=apply_update)
    # step 0 applies 0 (warmup), step t applies 0.5 * batch_{t-1}
    assert applied == [0.0, 0.5, 1.0, 1.5]


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def _mk_runner(inj, **kw):
    ckpt = {}

    def save(state, step):
        ckpt[step] = state

    def restore():
        if not ckpt:
            return None
        s = max(ckpt)
        return ckpt[s], s

    rr = ResilientRunner(lambda st, s: st + s, save_fn=save,
                         restore_fn=restore, every=2, injector=inj, **kw)
    return rr


def test_transient_retry():
    inj = FaultInjector(fail_at={(3, 0)})
    rr = _mk_runner(inj, max_retries=2)
    state, _ = rr.run(0, n_steps=6)
    assert state == sum(range(6))
    assert [e[0] for e in rr.events].count("failure") == 1
    assert not any(e[0] == "restore" for e in rr.events)


def test_restore_and_replay_exact():
    inj = FaultInjector(fail_at={(5, 0), (5, 1), (5, 2)})
    rr = _mk_runner(inj, max_retries=2)
    state, _ = rr.run(0, n_steps=10)
    assert state == sum(range(10))   # bitwise-identical replay
    assert any(e[0] == "restore" for e in rr.events)


def test_unrecoverable_raises():
    inj = FaultInjector(fail_at={(s, a) for s in range(3, 9)
                                 for a in range(4)})
    rr = _mk_runner(inj, max_retries=1, max_restores=2)
    with pytest.raises(StepFailure):
        rr.run(0, n_steps=10)


def test_straggler_detection():
    times = [0.001] * 8 + [0.05] + [0.001] * 3

    def step(st, s):
        time.sleep(times[s])
        return st + 1

    rr = ResilientRunner(step, straggler_factor=3.0)
    rr.run(0, n_steps=len(times))
    assert len(rr.stragglers) >= 1
    assert rr.stragglers[0][0] == 8


def test_heartbeat():
    hb = Heartbeat(timeout_s=0.05)
    hb.beat()
    assert not hb.expired
    time.sleep(0.08)
    assert hb.expired
    with pytest.raises(Exception):
        hb.check()
