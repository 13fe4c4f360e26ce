"""CPU rehearsal of every cell's traffic, task loop, correctness check and
end-to-end arithmetic, at tiny float32 sizes, with the look for a chip
steered to the CPU inside the test."""

import json
import os
import time

import pytest

from conftest import CELLS, REPO, make_root


@pytest.mark.parametrize("workload", [w for w, _, _ in CELLS])
def test_cell_runs_and_is_correct(tmp_path, on_cpu, workload):
    root = make_root(tmp_path)
    res = on_cpu.measure(root, workload, 2**31 + 11, 0.5, False,
                         time.monotonic())
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    wanted = {m["name"]: m["unit"] for m in spec["end_to_end"]
              if "workloads" not in m or workload in m["workloads"]}
    assert {k: v["unit"] for k, v in res["metrics"].items()} == wanted
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert res["compared"]["logit_gap"]["value"] <= 1e-4
    assert res["info"]["live_bytes_before_reference"] == 0
    assert res["info"]["engine"]["compiles"] == 0
    assert res["info"]["window_compiles"] == 0
    assert len(res["info"]["task_body_s"]) == res["attempted"] // 4
    reuse = res["info"]["engine"]["prefix_tokens_reused"] / (
        res["info"]["engine"]["prefix_tokens_reused"]
        + res["info"]["engine"]["prefill_tokens"])
    assert reuse > 0.5


def test_each_claim_prompt_ends_in_a_token_of_its_own():
    """The compared positions differ from claim to claim: every prompt
    ends in its claim's row number, and the prompts share the template."""
    from bench import claims
    t = json.load(open(os.path.join(REPO, "bench", "traffic",
                                    "factcheck.json")))
    s = claims.ClaimStream(t, 2**31 + 5, 49152)
    batch = s.batch(0, 64)
    assert len({p[-1] for p in batch}) == 64
    n = len(s._template.split()) + 1
    assert all(p[:n] == batch[0][:n] for p in batch)


def test_every_seed_gets_the_same_prompt_sizes():
    """The seed draws the prompts' words, not their lengths: the same
    work for every seed, so seeds differ no more than runs of one seed.
    A prompt's ids are its text's, though only its claim is hashed."""
    from bench import claims
    t = json.load(open(os.path.join(REPO, "bench", "traffic",
                                    "factcheck.json")))
    seeds = [1, 2, 2**31 + 5, 2**32 + 3, 4150000021, 4150000025]
    sizes = set()
    for seed in seeds:
        s = claims.ClaimStream(t, seed, 49152)
        batch = s.batch(0, 96) + s.batch(1 << 30, 32)
        assert all(p == claims.encode(s.text(i), 49152) for i, p in zip(
            list(range(96)) + list(range(1 << 30, (1 << 30) + 32)), batch))
        sizes.add(tuple(len(p) for p in batch))
    assert len(sizes) == 1
    texts = {claims.ClaimStream(t, seed, 49152)._template for seed in seeds}
    assert len(texts) == len(seeds)


def test_a_compile_inside_the_window_is_counted(on_cpu):
    import jax
    import jax.numpy as jnp
    x = jnp.arange(7)
    counter = on_cpu.compile_counter()
    counter.on, counter.count = True, 0
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    counter.on = False
    assert counter.count == 1
    jax.jit(lambda x: x * 5)(x).block_until_ready()
    assert counter.count == 1


def test_same_seed_same_claims_and_a_result_line(tmp_path, on_cpu, capsys):
    from bench import claims, weights
    t = json.load(open(os.path.join(REPO, "bench", "traffic",
                                    "factcheck.json")))
    big = 2**33 + 7
    a, b = claims.ClaimStream(t, big, 512), claims.ClaimStream(t, big, 512)
    assert a.batch(0, 8) == b.batch(0, 8)
    assert a.batch(0, 8) != claims.ClaimStream(t, big + 1, 512).batch(0, 8)
    assert (weights.seed_key(big) == weights.seed_key(big)).all()
    assert not (weights.seed_key(big) == weights.seed_key(big + 1)).all()
    root = make_root(tmp_path)
    res = on_cpu.measure(root, "tiny.factcheck", big, 0.3, False,
                         time.monotonic())
    on_cpu.report(res)
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert list(last)[-1] == "compared"
    assert err.strip().splitlines()[-1].startswith("compared logit_gap")
