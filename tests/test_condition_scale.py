"""Distinct-condition scale: kernel templating keeps the device graph small.

Per-policy distinct conditions must not explode the jit graph. Kernels
identical up to literals share one template; the traced subgraph count is
O(templates), not O(conditions) (on a CPU host of an earlier round 2,000
kernels took 126 s of XLA compile untemplated, seconds templated).
"""

import numpy as np
import pytest

from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import CheckInput, EvalParams, Principal, Resource
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input
from cerbos_tpu.tpu import TpuEvaluator


def distinct_condition_corpus(n: int) -> str:
    docs = []
    for i in range(n):
        docs.append(f"""
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: res{i}
  version: default
  rules:
    - actions: ["view"]
      effect: EFFECT_ALLOW
      roles: [user]
      condition:
        match:
          expr: R.attr.amount < {i * 7 + 3}
    - actions: ["edit"]
      effect: EFFECT_ALLOW
      roles: [user]
      condition:
        match:
          expr: R.attr.tier == "tier{i}" && R.attr.level >= {i % 97}
""")
    return "\n---\n".join(docs)


def scale_inputs(n_policies: int, count: int, seed: int = 0) -> list[CheckInput]:
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(count):
        i = int(rng.integers(0, n_policies))
        out.append(CheckInput(
            principal=Principal(id="u", roles=["user"]),
            resource=Resource(kind=f"res{i}", id="x", attr={
                "amount": float(rng.integers(0, 20000)),
                "tier": f"tier{int(rng.integers(0, n_policies))}",
                "level": float(rng.integers(0, 100)),
            }),
            actions=["view", "edit"],
        ))
    return out


N = 50  # 100 distinct condition kernels


@pytest.fixture(scope="module")
def scale_table():
    return build_rule_table(compile_policy_set(list(parse_policies(distinct_condition_corpus(N)))))


def test_kernels_group_into_templates(scale_table):
    ev = TpuEvaluator(scale_table, use_jax=False, min_device_batch=0)
    compiler = ev.lowered.compiler
    assert len(compiler.kernels) == 2 * N
    compiler.build_groups()
    # two rule shapes → two templates, regardless of policy count
    assert len(compiler.groups) == 2
    assert sorted(cid for g in compiler.groups for cid in g.cond_ids) == list(range(2 * N))


@pytest.mark.parametrize("use_jax", [False, True])
def test_scale_corpus_parity(scale_table, use_jax):
    ev = TpuEvaluator(scale_table, use_jax=use_jax, min_device_batch=0)
    params = EvalParams()
    inputs = scale_inputs(N, 256)
    got = ev.check(inputs, params)
    assert ev.stats["oracle_inputs"] == 0, "scale corpus must be fully device-served"
    for inp, g in zip(inputs, got):
        w = check_input(scale_table, inp, params)
        assert {a: (e.effect, e.policy) for a, e in g.actions.items()} == {
            a: (e.effect, e.policy) for a, e in w.actions.items()
        }


N_BIG = 5_000  # 10,000 distinct condition kernels


@pytest.fixture(scope="module")
def big_scale_table():
    return build_rule_table(
        compile_policy_set(list(parse_policies(distinct_condition_corpus(N_BIG))))
    )


def _steady_seconds(ev, inputs, params, iters=5) -> float:
    import time

    ev.check(inputs, params)  # warm: jit trace / caches
    ev.check(inputs, params)
    best = float("inf")
    for _ in range(iters):
        t0 = time.process_time()
        ev.check(inputs, params)
        best = min(best, time.process_time() - t0)
    return best


@pytest.mark.parametrize("use_jax", [False, True])
def test_10k_kernel_steady_state_within_2x(scale_table, big_scale_table, use_jax):
    """A batch referencing a sparse slice of a 10k-kernel
    table must run within 2x of the same batch against a 100-kernel table —
    on BOTH backends. The group-member variants make sat (and the jit trace)
    O(active conditions), so table size stops being a per-batch cost."""
    params = EvalParams()
    # same request slice (kinds 0..N-1) against both tables
    inputs = scale_inputs(N, 512)

    ev_small = TpuEvaluator(scale_table, use_jax=use_jax, min_device_batch=0)
    ev_big = TpuEvaluator(big_scale_table, use_jax=use_jax, min_device_batch=0)

    # parity first: the big table must decide the slice identically
    got = ev_big.check(inputs, params)
    assert ev_big.stats["oracle_inputs"] == 0
    for inp, g in zip(inputs, got):
        w = check_input(big_scale_table, inp, params)
        assert {a: (e.effect, e.policy) for a, e in g.actions.items()} == {
            a: (e.effect, e.policy) for a, e in w.actions.items()
        }

    t_small = _steady_seconds(ev_small, inputs, params)
    t_big = _steady_seconds(ev_big, inputs, params)
    assert t_big <= 2.0 * t_small + 0.005, (
        f"10k-kernel steady state {t_big * 1e3:.1f}ms vs "
        f"100-kernel {t_small * 1e3:.1f}ms exceeds 2x"
    )
