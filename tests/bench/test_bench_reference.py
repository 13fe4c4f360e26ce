"""The plain reference against the program's model and engine, on the CPU
at tiny float32 sizes of both configurations' shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import REPO, TINY, TINY_GQA


def _config(c):
    from bench import architectures
    c = dict(c)
    pad = c["vocab_pad_to"]
    c["padded_vocab"] = -(-c["vocab_size"] // pad) * pad
    c["file"], c["root"] = "tiny", REPO
    return c, architectures.of(c).program_config(c)


def _params(m, c, seed):
    from bench import weights
    return weights.make_params(jax.eval_shape(m.init, jax.random.PRNGKey(0)),
                               weights.dense_layout(c),
                               c["num_hidden_layers"], seed, jnp.float32)


@pytest.mark.parametrize("tiny", [TINY, TINY_GQA], ids=["mha", "gqa"])
def test_reference_matches_program_forward(tiny):
    from repro.models import build_model
    from bench import reference
    c, cfg = _config(tiny)
    m = build_model(cfg)
    params = _params(m, c, 7)
    rng = np.random.RandomState(0)
    toks = rng.randint(8, c["vocab_size"], size=(3, 40)).astype(np.int32)
    lens = np.array([40, 23, 9], np.int32)
    rows = np.stack([lens - 3, lens - 2, lens - 1], 1)
    with jax.default_matmul_precision("highest"):
        want, _ = m.forward(params, {"tokens": jnp.asarray(toks)})
    want = np.take_along_axis(np.asarray(want), rows[:, :, None], 1)
    got = reference.logits_at(c, 7, toks, lens, rows)
    np.testing.assert_allclose(got, want[..., :c["vocab_size"]],
                               rtol=1e-4, atol=1e-4)


def test_layer_by_layer_weights_equal_the_whole_tree():
    from repro.models import build_model
    from bench import weights
    c, cfg = _config(TINY_GQA)
    m = build_model(cfg)
    p = _params(m, c, 2**33 + 1)
    make = weights.layer_maker(weights.dense_layout(c), 2**33 + 1,
                               jnp.float32)
    for layer in range(c["num_hidden_layers"]):
        w = make(layer)
        np.testing.assert_array_equal(w["wk"], p["layers"]["attn"]["wk"][layer])
        np.testing.assert_array_equal(w["down"], p["layers"]["mlp"]["down"][layer])
        np.testing.assert_array_equal(w["ln2"], p["layers"]["ln2"]["scale"][layer])
    top = make(-1)
    np.testing.assert_array_equal(top["embed"], p["embed"]["tok"])
    assert not np.array_equal(make(0)["wq"], make(1)["wq"])


def test_engine_tokens_have_no_gap_and_a_wrong_token_has_one():
    """Greedy tokens served through the paged engine (prefill, shared
    prefix, megastep decode) are the reference's own at float32; the
    control, one precision lower, reads a gap."""
    from repro.models import build_model
    from repro.serving import InferenceEngine
    from repro.serving.request import Request
    from bench import check, claims
    c, cfg = _config(TINY)
    m = build_model(cfg)
    params = _params(m, c, 3)
    eng = InferenceEngine(m, params, slots=4, cache_len=256,
                          prefill_buckets=(32, 256), megastep=8, paged=True)
    stream = claims.ClaimStream({"prompt": "claim : {claim} . answer :",
                                 "shots": 8},
                                3, c["vocab_size"])
    prompts = stream.batch(0, 8)
    reqs = [eng.submit(Request(prompt=p, max_new_tokens=4, stop_tokens=()))
            for p in prompts]
    eng.run_to_completion()
    assert eng.stats.prefix_hits > 0
    answers = [(p, r.generated) for p, r in zip(prompts, reqs)]
    got = check.logit_gap(c, 3, answers, control=True)
    assert got["logit_gap"] < 1e-4
    assert got["control_logit_gap"] >= 0.0
    wrong = [(p, [(g[0] + 1) % c["vocab_size"]] + g[1:]) for p, g in answers]
    assert check.logit_gap(c, 3, wrong)["logit_gap"] > 1e-3
