"""The comparison that decides `correct`, at a size a test run holds: the
plain reference agrees with the program's oracle, a sound run passes,
and the lower-precision control and the planted faults fail.

The runs skip the harness's look for a chip (`bench.run.run` is called
directly) and drive the rest of a run on the CPU."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench import run as brun

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 33 + 12345          # more than 32 bits, as the driver's are


@pytest.fixture(scope="module", autouse=True)
def program_on_path():
    brun.import_program(ROOT)


def test_reference_ranks_agree_with_the_program_oracle():
    from repro.core.exact import exact_ranks
    ku, ki, kq = jax.random.split(jax.random.PRNGKey(0), 3)
    users = jax.random.normal(ku, (700, 24), jnp.float32)
    items = jax.random.normal(ki, (300, 24), jnp.float32)
    qs = jax.random.normal(kq, (5, 24), jnp.float32)
    ours = np.asarray(reference.exact_ranks(users, items, qs, block=256))
    for j in range(qs.shape[0]):
        assert np.array_equal(ours[j], np.asarray(
            exact_ranks(users, items, qs[j])))


def test_reference_bracket_agrees_with_the_program_table():
    from repro.core import rank_table
    from repro.core.query import (lookup_bounds_batch, query_batch,
                                  user_scores_batch)
    from repro.core.types import RankTableConfig
    ku, ki, kq, kb = jax.random.split(jax.random.PRNGKey(1), 4)
    users = jax.random.normal(ku, (900, 24), jnp.float32)
    items = jax.random.normal(ki, (333, 24), jnp.float32)
    qs = jax.random.normal(kq, (6, 24), jnp.float32) * 1.3
    cfg = RankTableConfig(tau=40, omega=10, s=16)
    rt = rank_table.build_rank_table(users, items, cfg, kb)
    scores, slack = user_scores_batch(users, qs)
    lo, up, est = lookup_bounds_batch(rt, scores, slack)
    samples, w = reference.samples_and_weights(items, kb, 10, 16)
    rlo, rup, rest = reference.table_bounds(
        users, samples, w, qs, 333, tau=40, range_pad=cfg.range_pad,
        block=256)
    assert np.array_equal(np.asarray(rlo), np.asarray(lo).T)
    assert np.array_equal(np.asarray(rup), np.asarray(up).T)
    assert np.allclose(np.asarray(rest), np.asarray(est).T, atol=1e-3)
    # the reference's own §4.3 selection picks the program's users
    res = query_batch(rt, users, qs, k=7, c=2.0)
    for j in range(qs.shape[0]):
        sel = reference.select(np.asarray(rlo[j]), np.asarray(rup[j]),
                               np.asarray(rest[j]), k=7, c=2.0, m=333)
        assert set(sel.tolist()) == set(np.asarray(res.indices[j]).tolist())


def tiny_plan(cell: str) -> dict:
    plan = brun.resolve(brun.load_json(os.path.join(ROOT, "BENCHMARK.json")),
                        cell, ROOT)
    plan["config"].update(n_users=2048, n_items=400, d=16, tau=48,
                          backend="dense" if plan["config"]["backend"]
                          == "fused" else "pruned:dense")
    plan["config"]["check"] = dict(plan["config"]["check"],
                                   sampled_answers=8)
    plan["mix"]["warm_seconds"] = 0.2
    if plan["mix"]["loop"] == "open":
        plan["mix"]["rate_qps"] = 60.0
    return plan


def one_run(cell, **kw):
    return brun.run(tiny_plan(cell), SEED, 2.0, False, log=lambda _: None,
                    **kw)


@pytest.mark.parametrize("cell", ["netflix.sweep", "movielens.promo-open"])
def test_a_sound_run_is_correct(cell):
    res = one_run(cell)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"


def test_the_bf16_control_is_not_correct():
    res = one_run("netflix.sweep", storage="bf16")
    assert not res["correct"]
    assert res["check"]["bounds_off"]["value"] > 0.5


def _alter_answer(res):
    idx = res.indices
    n = res.r_lo.shape[-1]
    return res._replace(indices=idx.at[:, 0].set((idx[:, 0] + n // 2) % n))


def _drop_half(res):
    b = res.indices.shape[0]
    keep = np.concatenate([np.arange(b // 2), np.arange(b // 2)])
    keep = np.concatenate([keep, np.arange(keep.size, b)])
    return jax.tree_util.tree_map(lambda x: x[keep], res)


@pytest.mark.parametrize("fault", [_alter_answer, _drop_half])
def test_a_fault_in_the_timed_path_is_not_correct(fault, monkeypatch):
    from repro.core import ReverseKRanksEngine
    real = ReverseKRanksEngine.dispatch_batch_at

    def broken(self, snap, qs, k, c):
        return fault(real(self, snap, qs, k, c))

    monkeypatch.setattr(ReverseKRanksEngine, "dispatch_batch_at", broken)
    res = one_run("netflix.sweep")
    assert not res["correct"], res["check"]
