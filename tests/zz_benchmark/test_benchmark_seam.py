"""The seam a configuration's module states its task through, on a
fixture that is no image classifier (``fixtures/configs/token_toy``:
token records, a next-token loss with a second term, AdamW, fan-ins of
its own). Everything goes through the harness's own functions, so a key
of a configuration's file that they read and the fixture lacks fails
here."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import cells, check, datagen, driver, harness, reference

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2 ** 31 + 26          # the driver's seeds pass 32 signed bits
IMAGE_KEYS = {"image_size", "crop_size", "num_channels", "num_classes",
              "decode"}


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """The fixture's one cell from a root of its own, as the harness finds
    it, with its records written through the program's paths and the
    reference's K steps from the seed's weights: ``(cell, task, records,
    start params, start state, reference's result)``."""
    root = str(tmp_path_factory.mktemp("toy") / "root")
    shutil.copytree(os.path.join(HERE, "fixtures"),
                    os.path.join(root, "fixtures"))
    bench = {"configs": [{"name": "token_toy",
                          "file": "fixtures/configs/token_toy.json"}],
             "workloads": [{"name": "token_toy_b8", "config": "token_toy",
                            "traffic": "b8_s16_k3", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = cells.load_cell(root, "token_toy_b8")
    assert not IMAGE_KEYS & set(cell.config)
    task = harness.task_of(cell)
    flags = harness.program_flags(cell, os.path.join(root, "work"))
    records = harness.write_records(cell, task, SEED, flags)
    return (cell, task, records) + _run(cell, task, records)


def _run(cell, task, records, numerics=None):
    """The reference's K steps, as ``run_cell`` and ``calibrate.py`` call
    them."""
    return harness.reference_chunk(
        cell, task, harness.hyper_of(cell), SEED, jax.devices()[:1],
        cell.reference.param_shapes(cell.config), records, numerics=numerics)


def _in_blocks(cell, blocks):
    task = harness.task_of(cell._replace(
        config={**cell.config, "reference_grad_blocks": blocks}))
    assert task.grad_blocks == blocks
    return task


def test_a_token_configuration_runs_end_to_end_through_the_harness(toy):
    cell, task, records, p0, s0, ref = toy
    assert records.shape == (64, 17) and records.dtype == np.int32
    assert set(ref.opt) == {"mu", "nu"} and np.all(np.isfinite(ref.losses))
    # the same steps with the gradient taken in two blocks, in the
    # program's place
    _, _, other = _run(cell, _in_blocks(cell, 2), records)
    numbers = check.compare(driver.in_the_programs_place(other), p0, s0, ref)
    correct, compared = check.verdict(numbers, cell.limits)
    assert correct, compared
    assert set(compared) == set(cell.limits)
    assert {"nu", "nu_mid", "nu_diff", "nu_diff_mid", "mu_diff_mid"} \
        <= set(numbers)
    # examples and FLOPs an example, as the harness's ctx reads them
    assert cell.reference.train_flops_per_image(cell.config) > 0
    assert cell.reference.param_count(cell.config) \
        == cell.config["parameters"]


def test_the_k_steps_are_the_loop_written_out(toy):
    cell, task, records, p0, _, ref = toy
    hyper, spec = harness.hyper_of(cell), cell.config
    flags = harness.cell_flags(cell)
    b1, b2, eps = (spec["adam"][k] for k in ("b1", "b2", "eps"))
    nm = reference.Numerics("float32")
    rows = reference.stream_rows(hyper.seed, 0, hyper.steps * hyper.batch,
                                 hyper.records).reshape(hyper.steps, -1)
    p = jax.tree.map(jnp.asarray, p0)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses = []
    with jax.default_matmul_precision("highest"):
        for i in range(hyper.steps):
            tokens = jnp.asarray(records[rows[i]])
            (loss, _), g = jax.value_and_grad(
                lambda pp: task.loss(nm, pp, {}, (tokens[:, :-1],
                                                  tokens[:, 1:])),
                has_aux=True)(p)
            losses.append(float(loss))
            if i == 0:
                first = g
            m = jax.tree.map(lambda a, gg: b1 * a + (1 - b1) * gg, m, g)
            v = jax.tree.map(lambda a, gg: b2 * a + (1 - b2) * gg * gg, v, g)
            p = jax.tree.map(
                lambda pp, a, c: pp - flags["learning_rate"] * (
                    a / (1 - b1 ** (i + 1))
                    / (jnp.sqrt(c / (1 - b2 ** (i + 1))) + eps)
                    + flags["weight_decay"] * pp), p, m, v)
    np.testing.assert_allclose(ref.losses, losses, rtol=2e-6)
    for mine, theirs in ((p, ref.params), (m, ref.opt["mu"]),
                         (v, ref.opt["nu"])):
        for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-7)
    for a, b in zip(jax.tree.leaves(first),
                    jax.tree.leaves(ref.first_grad_norms)):
        assert float(jnp.linalg.norm(a)) == pytest.approx(float(b), rel=1e-5)
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("blocks", [2, 4])
def test_a_gradient_taken_in_blocks_is_the_same_gradient(toy, blocks):
    cell, _, records, p0, s0, ref = toy
    _, _, blocked = _run(cell, _in_blocks(cell, blocks), records)
    numbers = check.compare(driver.in_the_programs_place(blocked), p0, s0,
                            ref)
    assert max(numbers.values()) < 1e-4, numbers
    np.testing.assert_allclose(
        jax.tree.leaves(blocked.first_grad_norms),
        jax.tree.leaves(ref.first_grad_norms), rtol=1e-5)
    with pytest.raises(ValueError, match="does not divide"):
        _run(cell, _in_blocks(cell, 3), records)


@pytest.mark.parametrize("what", ["state_unchanged", "half_batch",
                                  "bfloat16"])
def test_a_fault_and_the_control_pass_a_limit_the_sound_run_stays_under(
        toy, what):
    cell, task, records, p0, s0, ref = toy
    if what == "state_unchanged":
        zeros = jax.tree.map(np.zeros_like, p0)
        broken = driver.FirstDispatch(float(ref.losses[0]), p0, s0,
                                      {"mu": zeros, "nu": zeros})
    elif what == "half_batch":
        broken = driver.in_the_programs_place(
            _run(cell, task.fault("half_batch"), records)[2])
    else:
        broken = driver.in_the_programs_place(
            _run(cell, task, records, numerics="bfloat16")[2])
    # (the sound run stays under every limit: the first test)
    correct, compared = check.verdict(
        check.compare(broken, p0, s0, ref), cell.limits)
    assert not correct, compared
    assert "nu_diff_mid" in compared       # AdamW's second moment is read
    if what == "state_unchanged":
        assert compared["dparam"][0] == pytest.approx(1.0)
    with pytest.raises(ValueError, match="unknown fault"):
        task.fault("no_such_fault")


@pytest.mark.parametrize("mode", sorted(reference.Numerics.MODES))
def test_einsum_forms_a_product_as_dense_does(mode):
    kx, kw, kc = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(kx, (8, 32))
    w = jax.random.normal(kw, (32, 16))
    c = jax.random.normal(kc, (8, 16))
    nm = reference.Numerics(mode)
    np.testing.assert_array_equal(nm.einsum("ab,bc->ac", x, w),
                                  nm.dense(x, w))
    for mine, theirs in zip(
            jax.grad(lambda a, b: jnp.sum(nm.einsum("ab,bc->ac", a, b) * c),
                     argnums=(0, 1))(x, w),
            jax.grad(lambda a, b: jnp.sum(nm.dense(a, b) * c),
                     argnums=(0, 1))(x, w)):
        np.testing.assert_allclose(mine, theirs, rtol=1e-6, atol=1e-6)
    # a batched product: each expert's own matrix, rounded as the mode says
    e = jax.random.normal(kw, (3, 32, 16))
    per_expert = jnp.stack([nm.dense(x, e[i]) for i in range(3)], axis=1)
    # (float8 scales a tensor by its largest entry: the stacked experts
    # share one scale, each alone has its own)
    tight = 0.5 if mode == "float8" else 1e-6
    np.testing.assert_allclose(nm.einsum("td,edh->teh", x, e), per_expert,
                               rtol=tight, atol=tight * 3)


def test_a_configuration_states_the_fan_in_of_its_own_leaves(toy):
    cell = toy[0]
    spec = cell.config
    shapes = cell.reference.param_shapes(spec)
    stated = harness.make_params(cell, SEED, shapes)
    plain = datagen.make_params(SEED, shapes)
    d, e = spec["hidden_size"], spec["num_experts"]
    assert float(jnp.std(stated["embed"])) == pytest.approx(
        np.sqrt(0.5 / d), rel=0.05)
    gate = stated["layers"][1]["experts"]["gate"]
    assert float(jnp.std(gate)) == pytest.approx(np.sqrt(0.5 / d), rel=0.05)
    np.testing.assert_allclose(
        gate, plain["layers"][1]["experts"]["gate"] * np.sqrt(e), rtol=1e-6)
    # a leaf it states nothing of keeps the rule, bit for bit
    np.testing.assert_array_equal(stated["head"], plain["head"])
    np.testing.assert_array_equal(stated["final_norm"]["scale"],
                                  plain["final_norm"]["scale"])
