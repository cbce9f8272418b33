"""End-to-end behaviour tests: the paper's §4 A-to-Z pipeline, reproduced.

Listing 2 (embed the model) -> Listing 3 (replication + median) ->
Listing 4 (NSGA-II calibration) -> Listing 5 (island distribution), all on
the reduced ants config, plus packaging (CARE analogue) and the LM
hyper-parameter exploration use case.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.ants import simulate, simulate_batch
from repro.configs.ants_netlogo import BOUNDS, REDUCED
from repro.core import (Capsule, Context, JaxTask, PyTask, ToStringHook, Val,
                        aggregate, explore, puzzle)
from repro.evolution import NSGA2Config, pareto_front, run_generational
from repro.explore import (SeedSampling, StatisticTask, median, replicated_batch)


def test_listing2_embed_and_run_model():
    food = [Val(f"food{i}", float) for i in (1, 2, 3)]

    def ants_fn(gDiffusionRate, gEvaporationRate, seed):
        obj = simulate(REDUCED, jax.random.key(seed), gDiffusionRate,
                       gEvaporationRate)
        return {"food1": obj[0], "food2": obj[1], "food3": obj[2]}

    ants = JaxTask("ants", ants_fn,
                   inputs=(Val("gDiffusionRate", float),
                           Val("gEvaporationRate", float), Val("seed", int)),
                   outputs=tuple(food),
                   defaults={"seed": 42, "gDiffusionRate": 50.0,
                             "gEvaporationRate": 10.0})
    hook = ToStringHook(*food, printer=lambda s: None)
    res = puzzle(Capsule(ants).hook(hook)).run()
    assert len(hook.seen) == 1
    ctx = list(res.values())[0][0]
    for f in food:
        assert 0 <= float(ctx[f.name]) <= REDUCED.max_ticks


def test_listing3_replication_median_pipeline():
    seed = Val("seed", int)
    food1 = Val("food1", float)
    med1 = Val("medNumberFood1", float)

    def ants_fn(ctx):
        obj = simulate(REDUCED, jax.random.key(int(ctx["seed"])), 50.0, 10.0)
        return {"food1": float(obj[0])}

    model_c = Capsule(PyTask("ants", ants_fn, inputs=(seed,),
                             outputs=(food1,)))
    stat_c = Capsule(StatisticTask("stat", [(food1, med1, median)]))
    head = Capsule(PyTask("head", lambda ctx: {}))
    res = (puzzle(head) >> explore(SeedSampling(seed, 5, seed=1))
           >> model_c >> aggregate() >> stat_c).run()
    out = res[stat_c][0]
    assert 0 <= out["medNumberFood1"] <= REDUCED.max_ticks


def test_listing4_nsga2_calibration_improves_over_random():
    """The GA must find (diffusion, evaporation) that empty sources faster
    than random parameters — the paper's optimisation claim in miniature."""
    eval_fn = replicated_batch(
        lambda keys, genomes: simulate_batch(REDUCED, keys, genomes[:, 0],
                                             genomes[:, 1]),
        n_replicates=3)
    cfg = NSGA2Config(mu=8, genome_dim=2, bounds=BOUNDS, n_objectives=3,
                      reevaluate=0.01)
    state = run_generational(cfg, eval_fn, jax.random.key(0), lam=8,
                             generations=4)
    # random baseline: same eval budget of random genomes
    n = int(state.evaluations)
    keys = jax.random.split(jax.random.key(99), n)
    lo, hi = cfg.lo(), cfg.hi()
    rand = jax.random.uniform(jax.random.key(5), (n, 2)) * (hi - lo) + lo
    rand_obj = np.asarray(eval_fn(keys, rand))
    best_ga = float(np.asarray(state.objectives)[:, 0].min())
    best_rand = float(rand_obj[:, 0].min())
    assert best_ga <= best_rand + 30, (best_ga, best_rand)
    # calibration output is a population, not a point (multi-objective)
    assert state.objectives.shape == (8, 3)


def test_packaging_roundtrip_bit_exact(tmp_path):
    """CARE analogue: a packaged task re-executes without its source."""
    from repro.core.packaging import load, manifest, package

    def task_fn(x):
        return jnp.sin(x) * 2.0 + jnp.cumsum(x)

    path = str(tmp_path / "bundle")
    x_spec = jax.ShapeDtypeStruct((32,), jnp.float32)
    package(task_fn, [x_spec], path, name="sin-task")
    rehydrated = load(path)
    x = jax.random.normal(jax.random.key(0), (32,))
    np.testing.assert_array_equal(np.asarray(rehydrated(x)),
                                  np.asarray(task_fn(x)))
    m = manifest(path)
    assert m["name"] == "sin-task" and m["nbytes"] > 0


def test_lm_hyperparameter_exploration_workflow():
    """The paper's use case on the LM substrate: explore learning rates of a
    tiny smollm via the workflow engine, pick the best."""
    from repro.launch.train import train_loop
    lr_val = Val("lr", float)
    loss_val = Val("final_loss", float)

    def probe(ctx):
        _, losses = train_loop("smollm-135m", reduced=True, steps=8,
                               batch=2, seq=32, lr=float(ctx["lr"]),
                               log_every=1000, printer=lambda *a, **k: None)
        return {"final_loss": float(np.mean(losses[-3:]))}

    head = Capsule(PyTask("head", lambda ctx: {}))
    probe_c = Capsule(PyTask("probe", probe, inputs=(lr_val,),
                             outputs=(loss_val,)))
    from repro.explore import GridSampling
    res = (puzzle(head)
           >> explore(GridSampling({lr_val: [1e-4, 3e-3]}))
           >> probe_c).run()
    losses = {c["lr"]: c["final_loss"] for c in res[probe_c]}
    assert len(losses) == 2
    assert all(np.isfinite(v) for v in losses.values())
