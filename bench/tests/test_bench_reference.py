"""The plain float32 reference against the program's own forward pass,
both in float32, at a small size on the CPU.  The reference imports
nothing of the program; this test is where the two meet."""
import json

import jax
import numpy as np
import pytest

from bench import weights
from bench.cells import load_module
from bench.tests.helpers import FIXTURES, REPO

BIG = 2 ** 32 + 5


def test_reference_matches_the_program_forward():
    from repro.configs.base import ModelConfig
    from repro.core.partition import PartitionPlan
    from repro.core.precision import PrecisionPolicy
    from repro.models import transformer as T

    cfg = json.loads((FIXTURES / "tiny.json").read_text())
    ref = load_module(REPO / "bench/reference/dense.py", "dense")
    mcfg = ModelConfig(**ref.program_kwargs(cfg))
    layout = jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0),
                                                 mcfg))
    params = weights.make(layout, "float32", BIG)
    seq = np.random.default_rng(0).integers(0, cfg["vocab_size"], 77)
    plan = PartitionPlan.uniform(mcfg.num_layers, PrecisionPolicy.fp32())
    with jax.default_matmul_precision("highest"):
        prog = np.asarray(T.forward(params, mcfg, seq[None], plan).logits[0])
    mine = ref.logits(params, cfg, seq)
    assert mine.shape == prog.shape
    # both float32 at the highest precision: only summation order differs
    np.testing.assert_allclose(mine, prog, rtol=2e-4, atol=2e-4)
    # the gap of each row's own argmax is exactly 0
    best = prog.argmax(-1)
    gap, arg = ref.score(params, cfg, seq, 0, best)
    assert gap.max() < 1e-3 and (arg == best).mean() > 0.95


def test_weights_come_from_the_seed_in_the_stated_dtype():
    from repro.configs.base import ModelConfig
    from repro.models import transformer as T
    cfg = json.loads((FIXTURES / "tiny.json").read_text())
    ref = load_module(REPO / "bench/reference/dense.py", "dense")
    mcfg = ModelConfig(**ref.program_kwargs(cfg))
    layout = jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0),
                                                 mcfg))
    a = weights.make(layout, "bfloat16", BIG)
    b = weights.make(layout, "bfloat16", BIG)
    c = weights.make(layout, "bfloat16", BIG + 1)
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(
        layout)
    assert all(x.dtype == "bfloat16" for x in la)
    assert all((x == y).all() for x, y in zip(la, lb))
    assert not (a["embed"] == c["embed"]).all()
    wq = np.asarray(a["layers"]["sub_0"]["mixer"]["wq"], np.float32)
    assert abs(wq.std() * np.sqrt(cfg["hidden_size"]) - 1) < 0.05


def test_context_follows_the_configuration_padding():
    """The reference pads a prompt as the configuration's departures
    state, from the pool's own keys, not as the program reports."""
    cfg = json.loads((FIXTURES / "tiny.json").read_text())
    ref = load_module(REPO / "bench/reference/dense.py", "dense")
    pool = {"prompt_len": 32, "prefill_chunk": 16}
    prompt = np.arange(1, 11, dtype=np.int32)
    ctx = ref.context(cfg, pool, prompt)
    assert len(ctx) == 32 and (ctx[:22] == 0).all()
    assert (ctx[22:] == prompt).all()
    assert len(ref.context(cfg, pool, np.ones(40, np.int32))) == 48
    assert len(ref.context(cfg, {"prompt_len": 32},
                           np.ones(40, np.int32))) == 64
    cfg["departures"]["prompt_padding"]["attended"] = False
    assert (ref.context(cfg, pool, prompt) == prompt).all()
    del cfg["departures"]
    assert (ref.context(cfg, pool, prompt) == prompt).all()


def test_departures_are_what_the_reference_runs():
    """stablelm-2's file states the published block on top and the
    program's block under ``departures``; the reference runs the
    latter, and refuses the published one it cannot run."""
    cfg = json.loads((REPO / "bench/configs/stablelm-1.6b.json")
                     .read_text())
    ref = load_module(REPO / "bench/reference/dense.py", "dense")
    assert cfg["partial_rotary_factor"] == 0.25 and cfg["use_qkv_bias"]
    assert cfg["reduced"] == []
    kw = ref.program_kwargs(cfg)
    assert kw["norm_eps"] == 1e-5 and kw["num_layers"] == 24
    for key in ("norm", "partial_rotary_factor", "use_qkv_bias"):
        published = dict(cfg, departures={
            k: v for k, v in cfg["departures"].items() if k != key})
        with pytest.raises(ValueError):
            ref.program_kwargs(published)
