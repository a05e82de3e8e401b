"""The comparison catches a broken timed path: each run drives a fixture
cell with a fault planted in the program under test, and ``correct``
comes out false.  (Kept in one file so that the heavy CPU runs of this
suite share one pytest-xdist worker.)"""
import jax.numpy as jnp
import pytest

from bench.tests.tiny import run_tiny



def _wrap_decode(program, change):
    model = program.model
    orig = model.decode_and_sample

    def bad(params, cache, last, rng, temps, greedy_only=False):
        toks, new = orig(params, cache, last, rng, temps,
                         greedy_only=greedy_only)
        return change(cache, toks, new)

    object.__setattr__(model, "decode_and_sample", bad)  # frozen dataclass


def serve_state_unchanged(program):
    """The decode step hands back the cache it was given."""
    _wrap_decode(program, lambda cache, toks, new: (toks, cache))


def serve_half_batch(program):
    """The decode step computes only the first half of the slots; the
    rest emit token 0."""
    def change(cache, toks, new):
        B = toks.shape[0]
        return jnp.where(jnp.arange(B) < B // 2, toks, 0), new
    _wrap_decode(program, change)


def serve_token_altered(program):
    """Every third position's token is changed where it is produced."""
    def change(cache, toks, new):
        V = program.cfg.vocab_size
        return jnp.where(cache["pos"] % 3 == 0, (toks + 1) % V, toks), new
    _wrap_decode(program, change)


@pytest.mark.parametrize("fault", [serve_state_unchanged, serve_half_batch,
                                   serve_token_altered])
def test_serving_fault_is_not_correct(fault):
    out = run_tiny("tiny.chat", hook=fault)
    assert out["correct"] is False
    c = out["checks"]["served_gap"]
    assert c["value"] > c["limit"]



def _wrap_step(program, bad):
    orig = program.make_train_step

    def make(model, opt, plan):
        return bad(orig(model, opt, plan))

    program.make_train_step = make


def train_state_unchanged(program):
    """The step returns the state it was given."""
    def bad(step):
        def f(state, batch):
            _, met = step(state, batch)
            return state, met
        return f
    _wrap_step(program, bad)


def train_half_batch(program):
    """The step takes the mean over the first half of the rows only."""
    def bad(step):
        def f(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {"tokens": batch["tokens"][:n]})
        return f
    _wrap_step(program, bad)


def train_answer_altered(program):
    """One leaf's update is applied twice where the step produces it."""
    def bad(step):
        def f(state, batch):
            new, met = step(state, batch)
            p, q = state["params"]["blocks"], new["params"]["blocks"]
            q = dict(q, mlp_wu=p["mlp_wu"] + 2 * (q["mlp_wu"] - p["mlp_wu"]))
            new = dict(new, params=dict(new["params"], blocks=q))
            return new, met
        return f
    _wrap_step(program, bad)


@pytest.mark.parametrize("fault", [train_state_unchanged, train_half_batch,
                                   train_answer_altered])
def test_training_fault_is_not_correct(fault):
    out = run_tiny("tiny.train", hook=fault)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for k, c in out["checks"].items()
               if k != "compiles_in_window")
