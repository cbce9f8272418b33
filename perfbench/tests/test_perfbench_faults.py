"""A run with its timed path broken underneath must come out not correct:
each fault a cell can have, planted in the program, at a tiny size on the
CPU (the harness's look for a chip skipped). On one chip there is no
exchange between chips to leave out; the stream over four chips runs on
four host devices in a process of its own."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny_cells  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_traces():
    """A planted fault must not outlive its test in JAX's trace caches."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def _state_unchanged_ants(monkeypatch):
    """Every tick returns the colony as it was. (The values pass through an
    operation each: a carry returned as is would leave JAX's loop.)"""
    import jax
    from repro.ants import model

    def step(cfg):
        def same(state, tick, d, e):
            return state._replace(
                chem=state.chem + 0.0, food=state.food + 0.0,
                ant_pos=state.ant_pos + 0, carrying=state.carrying | False,
                ticks_empty=state.ticks_empty + 0,
                rng=jax.vmap(jax.random.split)(state.rng)[:, 0])
        return same
    monkeypatch.setattr(model, "make_step", step)


def _half_the_batch(monkeypatch):
    """Only the first half of a batch's individuals is evaluated; the rest
    repeat its rows."""
    import jax.numpy as jnp
    import repro.explore as explore
    real = explore.replicated_batch

    def half(fn, n, reducer=jnp.median):
        inner = real(fn, n, reducer)

        def evaluate(keys, genomes):
            h = max(genomes.shape[0] // 2, 1)
            out = inner(keys[:h], genomes[:h])
            return jnp.concatenate([out, out])[:genomes.shape[0]]
        return evaluate
    monkeypatch.setattr(explore, "replicated_batch", half)


def _answers_altered(monkeypatch):
    """Every objective row is altered where it is produced."""
    import jax.numpy as jnp
    import repro.explore as explore
    real = explore.replicated_batch

    def altered(fn, n, reducer=jnp.median):
        inner = real(fn, n, reducer)
        return lambda keys, genomes: inner(keys, genomes) + 1.0
    monkeypatch.setattr(explore, "replicated_batch", altered)


@pytest.mark.parametrize("name,fault", [
    ("egi_init.chunk64", _state_unchanged_ants),
    ("egi_init.chunk64", _half_the_batch),
    ("egi_init.chunk64", _answers_altered),
    ("egi_init.single", _state_unchanged_ants),
    ("egi_init.single", _answers_altered),
], ids=["stream-state-unchanged", "stream-half-batch",
        "stream-answers-altered", "single-state-unchanged",
        "single-answers-altered"])
def test_a_broken_timed_path_is_not_correct(name, fault, monkeypatch,
                                            tmp_path):
    fault(monkeypatch)
    r = tiny_cells.run(tiny_cells.cell(name), tmp_path)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("fault,correct", [([], True), (["exchange"], False)],
                         ids=["sound", "exchange-left-out"])
def test_the_four_chip_stream_without_its_exchange_is_not_correct(
        fault, correct, tmp_path):
    here = os.path.dirname(os.path.abspath(__file__))
    p = subprocess.run([sys.executable, os.path.join(here, "four_chips.py"),
                        *fault, str(tmp_path)],
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    r = json.loads(p.stdout.splitlines()[-1])
    assert r["attempted"] > 0
    assert r["correct"] is correct, r["checks"]
