"""The benchmark's yardstick on the CPU: files, FLOPs, the reference, the
check and its faults, and the refusal to run without a TPU."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import ROOT, grads_by_name, smoke_cell

from chipbench import cell, check, data, flops, program
from chipbench import run as runner
from chipbench.calibrate import half
from chipbench.reference import decoder
from chipbench.reference import model as M
from chipbench.reference import train as reference

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_config_traffic_and_metric_file_is_there():
    for w in BENCH["workloads"]:
        c = cell.load(w["name"])
        assert c["config"]["name"] == w["config"]
        assert set(c["workload"]["check"]["limits"]) == set(check.NAMES)
        for m in c["per_layer"]:
            assert callable(cell.metric_reader(m["name"]))
    for cf in BENCH["configs"]:
        conf = json.loads((ROOT / cf["file"]).read_text())
        assert conf["source"] == cf["source"]
        assert sorted(conf["reduced"]) == sorted(cf["reduced"])
        assert set(conf["reduced"]) <= set(conf["config"])
    files = {p.stem for p in (cell.HERE / "workloads").glob("*.json")}
    assert files >= {w["name"] for w in BENCH["workloads"]}


def test_program_config_matches_each_file():
    for w in BENCH["workloads"]:
        program.program_config(cell.load(w["name"])["config"])


def test_bert_large_flops_per_token():
    conf = cell.load("bert-large.b32-s512")["config"]
    assert decoder.matmul_params(conf["config"]) == (24 * 12_582_912
                                                     + 1024 * 30522)
    want = 6 * 333_244_416 + 12 * 24 * 512 * 1024
    assert flops.flops_per_token(conf, 512) == want
    assert abs(want - 2.15e9) / 2.15e9 < 0.01


def test_batches_are_seeded_and_rows_differ():
    tr = cell.load("bert-large.b32-s512")["traffic"]
    a = data.make_batch(tr, 30522, 2**31 + 11, 1)
    b = data.make_batch(tr, 30522, 2**31 + 11, 1)
    c = data.make_batch(tr, 30522, 2**31 + 12, 1)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert len({r.tobytes() for r in a["tokens"]}) == tr["batch"]
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["targets"][:, :-1])
    assert 0.9 < a["mask"].mean() < 1.0


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         "bert-large.b32-s512", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_reference_agrees_with_the_programs_model(cell_name):
    """Same loss and gradients as ``LayeredModel`` in float32, from the
    same weights, laid out as the program lays out its own."""
    from repro.models.model import LayeredModel
    c = smoke_cell(cell_name)
    conf = c["config"]
    cfg = program.program_config(conf).replace(dtype="float32")
    model = LayeredModel(cfg)
    key = runner.run_key(2**31 + 3)
    params = M.make_params(cell.family(conf), conf["config"], key)
    assert jax.tree.structure(params) == jax.tree.structure(
        model.abstract_params())
    b = data.make_batch(c["traffic"], conf["config"]["vocab_size"], 7, 0)
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(
            lambda p: model.full_loss(p, {k: jnp.asarray(v)
                                          for k, v in b.items()}),
            has_aux=True)(params)
    want = reference.train(conf, key, [b])
    assert abs(float(loss) - want["losses"][0]) < 1e-5 * float(loss)
    got = grads_by_name(g)
    assert set(got) == set(want["grad_norms"])
    med = np.median(list(want["grad_norms"].values()))
    for k, v in want["grad_norms"].items():
        assert abs(got[k] - v) <= 1e-3 * max(v, med), k


# A sound float32 run reads loss_gap up to 4e-4 at the third step at this
# size (Adam's first steps amplify round-off), grad_gap 4e-5 and
# change_gap 4e-3; a half batch reads grad_gap above 0.3, a state left
# unchanged 1.
FLOAT32_LIMITS = {"loss_gap": 2e-3, "grad_gap": 1e-2, "change_gap": 5e-2}


def _float32_cell(name):
    """The smoke cell with the program computing in float32, so that a
    sound run reads near 0 and any fault far above it."""
    c = smoke_cell(name)
    c["config"]["config"]["compute_dtype"] = "float32"
    c["config"]["program"]["replace"] = {"dtype": "float32"}
    c["workload"]["check"]["limits"] = FLOAT32_LIMITS
    return c


class _Broken:
    """The engine with its train step broken underneath."""

    def __init__(self, eng, fault):
        self._eng, self._fault = eng, fault

    def __getattr__(self, name):
        return getattr(self._eng, name)

    def train_step(self, state, batch):
        if self._fault == "unchanged":
            _, m = self._eng.train_step(jax.tree.map(jnp.copy, state), batch)
            return state, m
        n = batch["mask"].shape[0] // 2
        mask = batch["mask"].at[n:].set(0.0)
        return self._eng.train_step(state, dict(batch, mask=mask))


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch"])
def test_a_broken_step_is_not_correct(cell_name, fault, monkeypatch):
    build = program.build
    if fault:
        monkeypatch.setattr(program, "build",
                            lambda *a: _Broken(build(*a), fault))
    with jax.default_matmul_precision("highest"):
        out = runner.run(_float32_cell(cell_name), 2**31 + 5, 0.5, False)
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_the_control_is_not_correct(cell_name):
    """The reference in fp8, in the program's place, fails the cell's own
    limits against the float32 reference."""
    c = smoke_cell(cell_name)
    conf = c["config"]
    key = runner.run_key(2**31 + 17)
    batches = data.make_batches(c["traffic"], conf["config"]["vocab_size"],
                                2**31 + 17)[:3]
    want = reference.train(conf, key, batches)
    ctl = reference.train(conf, key, batches, arith="fp8")
    ok, rows = check.verdict(check.gaps(ctl, want),
                             c["workload"]["check"]["limits"])
    assert not ok, rows


def test_half_batch_reference_reads_as_a_fault(cell_name):
    c = smoke_cell(cell_name)
    conf = c["config"]
    key = runner.run_key(5)
    batches = data.make_batches(c["traffic"], conf["config"]["vocab_size"],
                                5)[:3]
    want = reference.train(conf, key, batches)
    same = reference.train(conf, key, batches)
    hb = reference.train(conf, key, [half(b) for b in batches])
    assert check.verdict(check.gaps(same, want), FLOAT32_LIMITS)[0]
    assert not check.verdict(check.gaps(hb, want), FLOAT32_LIMITS)[0]


def test_trace_reduction_matches_a_count_of_every_nanosecond():
    """A cut of a real v5e trace of ``bert-large.b32-s512`` (two slices:
    the host-side copies that end a step, and a step boundary)."""
    from chipbench import trace
    ev = json.loads((ROOT / "chipbench" / "tests" /
                     "trace_small.json").read_text())
    r = trace.reduce(ev)
    steps = [(s, s + d) for n, s, d in ev["host_spans"] if n == "step"]
    lo, hi = int(min(s for s, _ in steps)), int(max(e for _, e in steps))
    busy, wait, work = (np.zeros(hi - lo, bool) for _ in range(3))
    for name, s, d, host in ev["device_ops"]:
        if name.startswith("while"):
            continue
        a, b = max(int(s), lo) - lo, min(int(s + d), hi) - lo
        busy[a:b] = True
        waits = host and name.endswith(("-start", "-done"))
        (wait if waits else work)[a:b] = True
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert r["busy_s"] == pytest.approx(busy.sum() * 1e-9)
    assert r["relay_wait_s"] == pytest.approx((wait & ~work).sum() * 1e-9)
    # the slices were cut where both are large: 30% idle, 37% relay wait
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.3001, abs=1e-3)
    assert r["relay_wait_s"] / r["window_s"] == pytest.approx(0.3731,
                                                              abs=1e-3)
    assert r["has_transfers"]
    assert r["idle_gaps"][0][0] == "block"
