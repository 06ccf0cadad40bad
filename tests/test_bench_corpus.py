"""The memo-cold workload must preserve the replay workload's decisions.

requests_unique's whole claim (bench.py memo_cold) is
"unique values, same decision mix": every condition's truth value survives
the uniquification. This pins it by checking per-request effects against
the unjittered requests() the variant derives from.
"""

from cerbos_tpu.compile import compile_policy_set
from cerbos_tpu.engine import EvalParams
from cerbos_tpu.policy.parser import parse_policies
from cerbos_tpu.ruletable import build_rule_table, check_input
from cerbos_tpu.util import bench_corpus


def test_requests_unique_preserves_decisions():
    n_mods = 10
    rt = build_rule_table(
        compile_policy_set(list(parse_policies(bench_corpus.corpus_yaml(n_mods))))
    )
    params = EvalParams()
    base = bench_corpus.requests(384, n_mods, seed=5)
    uniq = bench_corpus.requests_unique(384, n_mods, seed=5)
    assert len(base) == len(uniq)
    mismatches = []
    for i, (b, u) in enumerate(zip(base, uniq)):
        assert b.actions == u.actions
        wb = check_input(rt, b, params)
        wu = check_input(rt, u, params)
        eb = {a: e.effect for a, e in wb.actions.items()}
        eu = {a: e.effect for a, e in wu.actions.items()}
        if eb != eu:
            mismatches.append((i, b.resource.kind, eb, eu))
    assert not mismatches, f"{len(mismatches)} decision flips, first: {mismatches[0]}"


def test_requests_unique_values_are_unique():
    uniq = bench_corpus.requests_unique(128, 10, seed=9)
    assert len({u.principal.id for u in uniq} | {u.resource.id for u in uniq}) == 2 * len(uniq)
    # numeric attrs differ across requests that share a base value
    scores = [u.resource.attr["score"] for u in uniq if "score" in u.resource.attr]
    assert len(set(scores)) == len(scores)
