"""A configuration's architecture is found by name: the dense decoder's
module gives what the modules it binds give, bit for bit, and a file that
names an architecture with no module, or one whose module is incomplete,
is refused before any device work."""

import json
import os

import numpy as np
import pytest

from conftest import REPO, TINY, TINY_GQA, make_root


def _config(tiny, root=REPO):
    c = dict(tiny)
    pad = c["vocab_pad_to"]
    c["padded_vocab"] = -(-c["vocab_size"] // pad) * pad
    c["file"], c["root"] = "tiny", root
    return c


@pytest.mark.parametrize("tiny", [TINY, TINY_GQA], ids=["mha", "gqa"])
def test_dense_decoder_module_is_what_it_binds(tiny):
    from bench import architectures, counts, reference, weights
    c = _config(tiny)
    arch = architectures.of(c)
    assert arch.layout(c) == weights.dense_layout(c)
    assert arch.weight_bytes(c) == counts.weight_bytes(c)
    assert arch.kv_bytes_per_token(c) == counts.kv_bytes_per_token(c)
    pre = [(0, 40), (155, 171), (155, 155 + 32)]
    assert arch.prefill_cost(c, pre, 3) == counts.prefill_cost(c, pre, 3)
    dec = [(171, 4), (40, 1), (187, 9)]
    assert arch.decode_cost(c, dec, 5) == counts.decode_cost(c, dec, 5)
    rng = np.random.RandomState(1)
    answers = [(list(rng.randint(8, c["vocab_size"], n)),
                list(rng.randint(8, c["vocab_size"], 4))) for n in (37, 9)]
    tokens, lens, rows = reference.sequences(answers)
    for precision in ("reference", "control"):
        np.testing.assert_array_equal(
            arch.logits_at(c, 5, tokens, lens, rows, precision=precision),
            reference.logits_at(c, 5, tokens, lens, rows,
                                precision=precision))


INCOMPLETE = '''"""An architecture module that provides only a check."""


def check(c):
    pass
'''


@pytest.mark.parametrize("case", ["unknown", "incomplete", "heads"])
def test_a_configuration_the_benchmark_cannot_run_is_refused_first(
        tmp_path, monkeypatch, case):
    """Refused by ``load_config``, before the look for a chip and before
    the context is built."""
    from bench import architectures, harness, model

    def touched(*a, **kw):
        raise AssertionError("device or context touched")
    monkeypatch.setattr(harness, "require_device", touched)
    monkeypatch.setattr(model, "build_context", touched)
    root = make_root(tmp_path)
    with open(os.path.join(root, "bench", "architectures",
                           "incomplete_decoder.py"), "w") as f:
        f.write(INCOMPLETE)
    path = os.path.join(root, "bench", "configs", "tiny.json")
    cfg = json.load(open(path))
    cfg.update({"unknown": {"architecture": "no_such_decoder"},
                "incomplete": {"architecture": "incomplete_decoder"},
                "heads": {"hidden_size": 66}}[case])
    json.dump(cfg, open(path, "w"))
    want = {"unknown": "no module for architecture 'no_such_decoder'",
            "incomplete": "provides no program_config, layout, logits_at",
            "heads": "not a multiple of the head count"}[case]
    with pytest.raises(ValueError, match=want) as err:
        harness.measure(root, "tiny.factcheck", 3, 0.3, False, 0.0)
    with pytest.raises(ValueError, match=want):
        model.load_config(path, root)
    if case == "unknown":
        present = str(err.value).rsplit(": ", 1)[1].split(", ")
        assert present == architectures.names(root)
        assert {"dense_decoder", "incomplete_decoder"} <= set(present)


def test_an_architecture_is_loaded_once_per_checkout(tmp_path):
    """Every caller of one run gets the same module object; another
    checkout gets its own."""
    from bench import architectures
    c = _config(TINY)
    assert architectures.of(c) is architectures.of(dict(c))
    other = _config(TINY, root=make_root(tmp_path))
    assert architectures.of(other) is not architectures.of(c)
    assert architectures.of(other).layout(other) == \
        architectures.of(c).layout(c)
