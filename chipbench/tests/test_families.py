"""Block families as files: layer groups, an auxiliary loss, and a
configuration that brings its own family without any harness file
changing (``two_groups.py`` and ``aux_family.py`` are test-only
families)."""
import copy
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
from conftest import ROOT, grads_by_name, smoke_cell

from chipbench import cell, data, flops, program
from chipbench import run as runner
from chipbench.reference import decoder
from chipbench.reference import model as M
from chipbench.reference import train as reference

CELL = "bert-large.b32-s512"


def test_two_layer_groups_train_bit_for_bit_as_one(monkeypatch):
    """The decoder's layers 1 and 2..n in two groups, from the same
    weights, give the same losses, gradient and change norms to the last
    bit, under the same names."""
    c = smoke_cell(CELL)
    conf = c["config"]
    conf["config"]["num_hidden_layers"] = 3
    key = runner.run_key(2**31 + 23)
    batches = data.make_batches(c["traffic"], conf["config"]["vocab_size"],
                                23)[:3]
    one = reference.train(conf, key, batches)

    two = dict(conf, reference="tests/two_groups.py")
    fam = cell.family(two)
    make = M.init_params

    def split_init(tree, std, key_):
        p = fam.split(make(decoder.param_tree(conf["config"]), std, key_))
        assert jax.tree.structure(p) == jax.tree.structure(
            jax.tree.map(lambda s: 0, tree, is_leaf=M._is_spec))
        return p

    monkeypatch.setattr(M, "init_params", split_init)
    _, layers, groups = reference.initial_params(fam, conf["config"], key)
    assert groups == [0, 1, 1] and len(layers) == 3
    got = reference.train(two, key, batches)
    assert got == one
    assert {k.split(".")[1] for k in got["grad_norms"]
            if k.startswith("layers.")} == {"0", "1", "2"}


def test_readings_number_layers_through_every_group():
    """``program._readings`` names group 0's row as ``layers.0`` and group
    1's rows as ``layers.1`` on, with no name taken twice."""
    rows = (1, 3)
    tree = {"embed": {"tok": {"m": jnp.ones((4, 2)), "v": jnp.ones((4, 2))}},
            "groups": tuple({"w": {"m": jnp.arange(r * 2.0).reshape(r, 2)
                                   + 10 * g, "v": jnp.zeros((r, 2))}}
                            for g, r in enumerate(rows)),
            "head": {"out": {"m": jnp.ones((2, 4)), "v": jnp.ones((2, 4))}}}
    out = program._readings(
        tree, lambda path, a: a[:, 0] if path.startswith("groups")
        else a.sum(), slot="m")
    assert out == {"embed.tok": 8.0, "head.out": 8.0, "layers.0.w": 0.0,
                   "layers.1.w": 10.0, "layers.2.w": 12.0,
                   "layers.3.w": 14.0}


def test_an_auxiliary_loss_matches_the_gradient_of_the_unblocked_loss(
        monkeypatch):
    """With a nonzero aux per sequence and the rows in two blocks, the
    reference's loss and first gradients are those of ``ce / W + sum over
    layers of aux`` over the whole batch, by ``jax.grad``."""
    monkeypatch.setattr(reference, "TOKENS_PER_ROW_BLOCK", 128)
    c = smoke_cell(CELL)
    conf = dict(c["config"], reference="tests/aux_family.py")
    fam, cc = cell.family(conf), conf["config"]
    key = runner.run_key(2**31 + 29)
    b = data.make_batch(c["traffic"], cc["vocab_size"], 29, 0)
    B, S = b["tokens"].shape
    assert B // max(1, reference.TOKENS_PER_ROW_BLOCK // S) == 2
    ar = M.Arith()
    W = float(np.sum(b["mask"]))

    def loss(p, with_aux=True):
        x = p["embed"]["tok"][jnp.asarray(b["tokens"])]
        aux = 0.0
        for g, stacked in enumerate(p["groups"]):
            for i in range(jax.tree.leaves(stacked)[0].shape[0]):
                x, a = fam.layer(cc, ar, jax.tree.map(lambda t: t[i],
                                                      stacked), x, S, g)
                aux = aux + a
        ce = M.head_loss_sum(cc, ar, p, x.reshape(B * S, -1),
                             jnp.asarray(b["targets"]).reshape(-1),
                             jnp.asarray(b["mask"]).reshape(-1))
        return ce / W + (aux if with_aux else 0.0)

    params = M.make_params(fam, cc, key)
    with jax.default_matmul_precision("highest"):
        want_loss, g = jax.value_and_grad(loss)(params)
        ce_only = loss(params, with_aux=False)
    got = reference.train(conf, key, [b])
    assert float(want_loss - ce_only) > 1e-2       # the aux is there
    assert abs(got["losses"][0] - float(want_loss)) < 1e-5 * float(want_loss)
    want = grads_by_name(g)
    assert set(want) == set(got["grad_norms"])
    med = np.median(list(want.values()))
    for k, v in want.items():
        assert abs(got["grad_norms"][k] - v) <= 1e-4 * max(v, med), k


def test_a_new_family_needs_only_new_files(tmp_path, monkeypatch):
    """A checkout with a configuration that names a test-only family, a
    traffic mix, a cell and their ``BENCHMARK.json`` entries, added as
    files to the harness as it is: loaded, checked against the program
    and FLOP-counted."""
    here = tmp_path / "chipbench"
    shutil.copytree(cell.HERE, here, ignore=shutil.ignore_patterns(
        ".out", "__pycache__"))
    bert = json.loads((cell.HERE / "configs" / "bert-large.json").read_text())
    conf = {"name": "toy", "source": bert["source"], "reduced": [],
            "config": {**bert["config"], **bert["smoke"]},
            "program": dict(bert["program"], variant="smoke"),
            "reference": "tests/aux_family.py",
            "optimizer": bert["optimizer"]}
    (here / "configs" / "toy.json").write_text(json.dumps(conf))
    (here / "traffic" / "s64.json").write_text(json.dumps(
        {"batch": 4, "seq": 64, "n_batches": 4, "doc_len": [16, 64],
         "motif_len": 8, "n_motifs": 64, "motif_frac": 0.7}))
    limits = {"loss_gap": 1e-3, "grad_gap": 1e-2, "change_gap": 5e-2}
    (here / "workloads" / "toy.s64.json").write_text(json.dumps(
        {"config": "toy", "traffic": "s64", "why": "a test",
         "check": {"steps": 3, "limits": limits}}))
    bench = copy.deepcopy(json.loads((ROOT / "BENCHMARK.json").read_text()))
    bench["workloads"].append({"name": "toy.s64", "config": "toy",
                               "traffic": "s64", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(cell, "HERE", here)
    monkeypatch.setattr(cell, "ROOT", tmp_path)

    c = cell.load("toy.s64")
    assert c["config"]["config"]["hidden_size"] == 128
    cfg = program.program_config(c["config"])
    assert (cfg.d_model, cfg.n_layers) == (128, 2)
    assert cell.family(c["config"]).SLOTS == 8
    assert flops.flops_per_token(c["config"], 64) == \
        decoder.flops_per_token(c["config"]["config"], 64)
