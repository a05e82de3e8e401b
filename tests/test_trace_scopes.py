"""Profile names the program gives its parts: the train step's and the
dense decoder's ``jax.named_scope`` scopes in the compiled programs'
op metadata, and stable names for the serving engine's programs."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.models import build_model
from repro.parallel.sharding import Plan
from repro.serve import ServeEngine
from repro.train import (OptimizerConfig, adamw_init, jit_train_step,
                         make_train_step)


@pytest.fixture(scope="module")
def qwen2():
    model = build_model(reduced(get_config("qwen2-1.5b")))
    params, _ = model.init(jax.random.PRNGKey(0))
    return model, params


def _op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def test_train_step_scopes_in_hlo_metadata(qwen2):
    model, params = qwen2
    opt = OptimizerConfig(lr=1e-3)
    step = jit_train_step(make_train_step(model, opt, Plan()), donate=False)
    state = {"params": params, "opt": adamw_init(params, opt),
             "step": jnp.zeros((), jnp.int32)}
    batch = {"tokens": jnp.ones((2, 16), jnp.int32)}
    names = _op_names(step.lower(state, batch).compile().as_text())
    parts = {p for n in names for p in n.split("/")}
    # forward, backward (the transpose of the forward) and optimizer
    assert {"jvp(train.loss)", "transpose(jvp(train.loss))",
            "train.optimizer"} <= parts
    assert {"lm.embed", "lm.attn", "lm.mlp", "lm.head"} <= parts
    # AdamW's update, square root included, is under the optimizer's scope
    assert any(n.startswith("jit(train_step)/train.optimizer/")
               and n.endswith("sqrt") for n in names)


def test_paged_decode_scopes_in_hlo_metadata(qwen2):
    model, params = qwen2
    eng = ServeEngine(model, params, max_batch=2, max_seq=32, eos_id=-1,
                      engine="paged", page_size=16)
    text = eng._decode_sample.lower(
        params, eng.cache, jnp.zeros((2, 1), jnp.int32), eng.base_key,
        jnp.zeros((2,), jnp.float32), greedy_only=True).compile().as_text()
    parts = {p for n in _op_names(text) for p in n.split("/")}
    assert {"lm.embed", "lm.attn", "lm.mlp", "lm.head"} <= parts


def test_engine_programs_have_stable_names(qwen2):
    model, params = qwen2
    eng = ServeEngine(model, params, max_batch=2, max_seq=32, eos_id=-1,
                      engine="paged", page_size=16, decode_chunk=2,
                      spec_k=2)
    assert eng._paged_insert_pad.__name__ == "paged_prefill_insert"
    assert eng._prefill_insert_pad.__name__ == "prefill_insert"
    assert eng._decode_chunk.__name__ == "decode_chunk"
    assert eng._spec_chunk.__name__ == "spec_chunk"
