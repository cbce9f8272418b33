"""The plain references against the program, on the CPU at small sizes,
and the lower-precision controls that must depart from them."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny_cells  # noqa: E402


def test_ants_reference_follows_simulate_batch_at_reduced():
    """At the program's REDUCED model, the reference reaches the same
    states as ``simulate_batch`` (read out through the benchmark's state
    tap): objectives, food taken per source, ants carrying, positions and
    the chemical field."""
    import jax
    from repro.configs.ants_netlogo import BOUNDS, REDUCED

    from entries import streaming_init
    from reference import ants as ants_ref

    model = {k: getattr(REDUCED, k) for k in tiny_cells.TINY_MODEL}
    config = {"model": model, "replicates": 3}
    keys, genomes = ants_ref.population_chunk(BOUNDS, tiny_cells.SEED, 3, 6)
    got = np.asarray(jax.jit(streaming_init.make_eval(config))(keys, genomes))
    want = np.asarray(jax.jit(
        lambda k, g: ants_ref.replicated(model, 3, k, g))(keys, genomes))
    # the colonies worked: food was carried off and chemical laid down
    assert (want[:, 3:6].sum(axis=1) > 0).all()
    assert (want[:, ants_ref.CHEM_SUM] > 0).all()
    numbers = ants_ref.compare(got, want)
    assert numbers == {"diverged": 0.0, "chem_gap": 0.0}


@pytest.mark.parametrize("name", ["egi_init.chunk64", "egi_init.single"])
def test_a_sound_run_is_correct(name, tmp_path):
    r = tiny_cells.run(tiny_cells.cell(name), tmp_path)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]


def test_the_tap_reads_simulate_batchs_own_computation():
    """The tapped program returns exactly the objectives ``simulate_batch``
    returns, beside the final state of every lane."""
    import jax
    from repro.ants import simulate_batch
    from repro.configs.ants_netlogo import BOUNDS, AntsConfig

    import tap
    from reference import ants as ants_ref

    cfg = AntsConfig(**tiny_cells.TINY_MODEL)
    keys, genomes = ants_ref.population_chunk(BOUNDS, tiny_cells.SEED, 1, 5)
    obj, state = jax.jit(lambda k, g: tap.tapped(
        simulate_batch, cfg, k, g[:, 0], g[:, 1]))(keys, genomes)
    want = simulate_batch(cfg, keys, genomes[:, 0], genomes[:, 1])
    np.testing.assert_array_equal(np.asarray(obj), np.asarray(want))
    w = cfg.world_size
    assert state["chem"].shape == (5, w, w)
    assert state["ant_pos"].shape == (5, cfg.population, 2)


def test_the_tap_refuses_a_tick_loop_that_is_not_the_ants_state():
    """A ``simulate_batch`` whose last loop carries something other than
    the ants state makes the tap raise instead of reading it."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro.configs.ants_netlogo import BOUNDS, AntsConfig

    import tap
    from reference import ants as ants_ref

    @functools.partial(jax.jit, static_argnums=0)
    def other(cfg, keys, diffusion, evaporation):
        total, _ = jax.lax.scan(lambda c, t: (c + diffusion, None),
                                jnp.zeros_like(diffusion),
                                jnp.arange(cfg.max_ticks))
        return total[:, None]

    cfg = AntsConfig(**tiny_cells.TINY_MODEL)
    keys, genomes = ants_ref.population_chunk(BOUNDS, tiny_cells.SEED, 1, 3)
    with pytest.raises(RuntimeError, match="not the ants state"):
        tap.tapped(other, cfg, keys, genomes[:, 0], genomes[:, 1])


def test_the_programs_bfloat16_field_departs_from_the_reference():
    """The control of the streamed cell: the model's own lower-precision
    path (``chem_dtype="bfloat16"``) through the timed chunk program, read
    as the limits were read (fixed chunks of one seed), fails a limit that
    the float32 program keeps."""
    import jax

    import readings

    c = tiny_cells.cell("egi_init.chunk64")
    limits = c.config["limits"]
    got = {kind: numbers for kind, _, numbers, _ in readings.streaming(
        c, [tiny_cells.SEED], [tiny_cells.SEED], jax.devices()[:1])}
    assert all(got["program"][n] <= limits[n] for n in limits)
    assert any(got["control"][n] > limits[n] for n in limits), got
