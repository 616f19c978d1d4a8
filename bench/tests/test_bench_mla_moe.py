"""The DeepSeek-V3-layer cell (moonlight-16b-a3b) against its plain
reference (``bench/reference/mla_moe.py``), its FLOP count and its
trace reduction by name scope, at small sizes on the CPU."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, flops_mla_moe, run, scope_reduce
from bench.kinds.train import program_config
from bench.kinds.train_mla_moe import MoeJob, program_view, published
from bench.reference import mla_moe as ref
from repro.models import init_params, moe
from repro.models.layers import MeshAxes

ROOT = Path(__file__).resolve().parents[2]
CELL = "moonlight-16b-a3b.train.1chip"
AX = MeshAxes(tp=1, dp=1, fsdp=False)


def smoke_files(dtype="bfloat16"):
    _, _, config, traffic = run.load_cell(CELL, True)
    config["program"] = {**config["program"],
                         "set": {**config["program"]["set"], "dtype": dtype}}
    return config, traffic


def test_program_matches_reference_over_three_steps(tmp_path):
    """The program in float32 against the float32 reference: the same
    equations, so only summation order differs (the losses to 1e-4, each
    leaf's first clipped gradient and three steps' change to 1 % of the
    larger of its and the median leaf's norm), and the reference's own
    top-k holds every pair the program chose."""
    config, traffic = smoke_files("float32")
    job = MoeJob(seed=2 ** 31 + 7, config=config, traffic=traffic,
                 devices=jax.devices()[:1], out=tmp_path)
    prog = job.checked_steps()
    job.free()
    want = job.reference()
    assert want["choice_gap"] == 0.0
    got = check.compare(prog, want, config["limits"])
    assert got["loss_gap"]["value"] < 1e-4, got
    assert got["grad_gap"]["value"] < 1e-2, got
    assert got["update_gap"]["value"] < 1e-2, got


def test_reference_holds_the_programs_leaves():
    """The same leaf paths and shapes, so that every leaf is compared."""
    config, _ = smoke_files()
    pub = published(config)
    cfg = program_config(program_view(config))
    have = ref.leaves(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, AX)[0]))
    want = ref.leaves(jax.eval_shape(lambda: ref.init(1, pub)))
    assert {k: v.shape for k, v in have.items()} == \
        {k: v.shape for k, v in want.items()}


def test_shares_add_up_to_the_uncut_layer():
    """The 8 shares of a layer's experts, each computed by the program as
    the chip that holds it (its experts relabelled 0..), with the shared
    experts counted once, give the uncut reference's MoE layer: float32,
    so summation order only (1e-5)."""
    config, _ = smoke_files()
    pub = published(config)
    shares, held = 8, pub["n_routed_experts"]
    uncut = {**pub, "n_routed_experts": shares * held,
             "deployment": {**pub["deployment"], "expert_parallel": 1}}
    d = ref.dims(uncut)
    p = jax.tree.map(lambda a: a[0], ref.init(3, uncut)["blocks"][0])["moe"]
    p["e_bias"] = jax.random.normal(jax.random.PRNGKey(9), p["e_bias"].shape
                                    ) * 0.05
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, d["D"]),
                          jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _, load, _ = ref.moe(p, x, d)
    cfg = program_config(program_view(config)).with_overrides(
        dtype="float32", n_experts=shares * held, experts_here=held,
        n_shared_experts=0)
    E = shares * held
    total = jnp.zeros_like(x)
    loads = jnp.zeros((E,))
    for s in range(shares):
        perm = (jnp.arange(E) + s * held) % E     # share s's experts first
        ps = {"router": p["router"][:, perm], "e_bias": p["e_bias"][perm],
              **{w: p[w][s * held:(s + 1) * held]
                 for w in ("w1", "w3", "w2")}}
        y, _, st = moe.dropless_block(ps, x, cfg, AX)
        total = total + y
        loads = loads + st["load"][jnp.argsort(perm)] / shares
    xt = x.reshape(-1, d["D"])
    hs = jax.nn.silu(xt @ p["shared_w1"]) * (xt @ p["shared_w3"])
    total = total + (hs @ p["shared_w2"]).reshape(x.shape)
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(loads, load)


def test_dropless_layer_equals_the_reference_when_all_pick_one_expert():
    """The router forced (its bias) so that every token's first choice is
    held expert 1: the program keeps all T pairs of that group, drops
    none, and gives the reference's layer (float32: 1e-5)."""
    config, _ = smoke_files()
    pub = published(config)
    d = ref.dims(pub)
    p = jax.tree.map(lambda a: a[0], ref.init(5, pub)["blocks"][0])["moe"]
    p["e_bias"] = p["e_bias"].at[1].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, d["D"]),
                          jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _, load, _ = ref.moe(p, x, d)
    cfg = program_config(program_view(config)).with_overrides(
        dtype="float32")
    y, _, st = moe.dropless_block(p, x, cfg, AX)
    assert int(st["max_expert_pairs"]) == 64 and float(load[1]) == 64
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)


def test_reference_routes_by_given_choices_and_counts_the_others():
    """Routed by the program's choices (here the program's own float32
    ones, from its stats), the reference's layer is its own, every pair
    found in its top-k; one pair moved to an expert outside it is counted
    and changes the layer (float32: 1e-5)."""
    config, _ = smoke_files()
    pub = published(config)
    d = ref.dims(pub)
    p = jax.tree.map(lambda a: a[0], ref.init(8, pub)["blocks"][0])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, d["D"]),
                          jnp.float32)
    cfg = program_config(program_view(config)).with_overrides(
        dtype="float32")
    _, _, st = moe.dropless_block(p, x, cfg, AX)
    choice = st["choices"]
    with jax.default_matmul_precision("highest"):
        free, aux, load, _ = ref.moe(p, x, d)
        same, aux2, load2, differ = ref.moe(p, x, d, choice=choice)
        other = next(e for e in range(d["E"]) if e not in choice[0])
        moved = choice.at[0, 0].set(other)
        y3, _, load3, differ3 = ref.moe(p, x, d, choice=moved)
    assert int(differ) == 0 and int(differ3) == 1
    np.testing.assert_allclose(same, free, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(aux2, aux, rtol=1e-6)
    np.testing.assert_array_equal(load2, load)
    assert float(load3[other]) == float(load[other]) + 1
    assert not np.allclose(y3[0, 0], free[0, 0], rtol=1e-3, atol=1e-5)


def test_choice_gap_fails_a_program_that_routes_to_the_wrong_experts(
        tmp_path, monkeypatch):
    """A program fault in the choice alone (the k lowest scores chosen,
    gates as before) reads a ``choice_gap`` far over the rehearsal's
    limit, where the sound program's passes."""
    config, traffic = smoke_files()
    limit = config["limits"]["choice_gap"]
    real = moe.route_scores

    def worst(p, xt, cfg):
        s, _, _ = real(p, xt, cfg)
        _, idx = jax.lax.top_k(-s, cfg.top_k)
        sel = jnp.take_along_axis(s, idx, axis=-1)
        return s, idx, sel / jnp.sum(sel, -1, keepdims=True) * \
            cfg.routed_scale

    gaps = {}
    for name in ("sound", "fault"):
        if name == "fault":
            monkeypatch.setattr(moe, "route_scores", worst)
        job = MoeJob(seed=2 ** 31 + 3, config=config, traffic=traffic,
                     devices=jax.devices()[:1], out=tmp_path / name)
        job.checked_steps()
        job.free()
        gaps[name] = job.reference()["choice_gap"]
    assert gaps["sound"] <= limit < 0.5 < gaps["fault"], gaps


def test_flops_hand_count():
    # per token, forward, at S=8192 with 0.75 held pairs per MoE layer:
    # MLA projections 2*2048*16*192 + 2*2048*576 + 2*512*16*256
    #   + 2*16*128*2048 = 27,525,120; attention 2*16*320*8193/2 =
    #   41,948,160; dense SwiGLU 2*3*2048*11264 = 138,412,032; shared
    #   2*3*2048*2816 = 34,603,008; router 2*2048*64 = 262,144; a pair
    #   2*3*2048*1408 = 17,301,504; head 2*2048*20480 = 83,886,080
    c = published(json.loads(
        (ROOT / "bench/configs/moonlight-16b-a3b.json").read_text()))
    f = flops_mla_moe.layer_flops_per_token(c, 8192)
    assert f == {"mla_proj": 27_525_120, "attention": 41_948_160,
                 "dense_mlp": 138_412_032, "shared": 34_603_008,
                 "router": 262_144, "expert_pair": 17_301_504,
                 "head": 83_886_080}
    fwd = flops_mla_moe.forward_flops_per_token(c, 8192, 5 * 0.75)
    want = 6 * (27_525_120 + 41_948_160) + 138_412_032 + \
        5 * (34_603_008 + 262_144) + 3.75 * 17_301_504 + 83_886_080
    assert fwd == want
    assert 0.87e9 < fwd < 0.89e9
    assert flops_mla_moe.train_flops_per_token(c, 8192, 3.75) == 3 * want


def test_scope_of_takes_the_innermost_and_the_ragged_kernel():
    scopes = ("repro.mla", "repro.moe.route", "repro.moe.experts")
    assert scope_reduce.scope_of(
        "jit(step)/transpose(jvp(repro.moe.route))/gather", scopes) == \
        "repro.moe.route"
    assert scope_reduce.scope_of(
        "jit(step)/repro.mla/repro.moe.experts/dot", scopes) == \
        "repro.moe.experts"
    assert scope_reduce.scope_of("ragged-dot-none", scopes) == \
        "repro.moe.experts"
    assert scope_reduce.scope_of("jit(step)/dot_general", scopes) == ""


def test_scope_reduce_reads_the_recorded_v5e_trace():
    """``bench/testdata/v5e_probe.xplane.pb``: three 1024^3 bf16 matmuls
    under ``probe_matmul`` (15.9 us each) and their copies."""
    ops = scope_reduce.device_ops(
        (ROOT / "bench/testdata/v5e_probe.xplane.pb").read_bytes())
    assert list(ops) == ["/device:TPU:0"]
    got = scope_reduce.reduce(ops, ("probe_matmul",))
    assert got["seconds"]["probe_matmul"] == pytest.approx(4.739e-5,
                                                           rel=1e-3)
    assert got["leaf_s"] == pytest.approx(
        got["seconds"]["probe_matmul"] + got["unattributed_s"])
    assert got["unattributed_s"] < 0.01 * got["leaf_s"]


def test_metric_readers_stay_silent_without_the_program_parts():
    """A run of a program without the counters or the scopes (the parent
    of this cell) reads None, never an error."""
    import importlib
    rec = {"steps": 3, "tokens": 3 * 8192, "window_s": 1.0, "seq": 8192,
           "chips": 1, "device_kind": "TPU v5 lite", "published": {},
           "rehearsal": False}
    for name in ("moe_step_mfu", "expert_gmm_roofline", "mla_attention_ms",
                 "moe_route_ms", "expert_load_imbalance",
                 "moe_device_idle_share"):
        assert importlib.import_module(f"bench.metrics.{name}").read(
            rec) is None


def test_expert_load_imbalance_reads_the_mean_largest_group():
    """2 window steps of 5 MoE layers x 8 held experts, 100 pairs a group
    on average, the largest group 150 in each step: 1.5."""
    from bench.metrics import expert_load_imbalance
    rec = {"published": {"num_hidden_layers": 6, "first_k_dense_replace": 1,
                         "n_routed_experts": 8},
           "moe_window": {"repro.moe.pairs_here": 2 * 40 * 100,
                          "repro.moe.max_expert_pairs": 2 * 150}}
    assert expert_load_imbalance.read(rec) == pytest.approx(1.5)


def test_traced_rehearsal_reports_the_cells_program_metrics():
    """The cell rehearsed with ``--trace 1`` reports the program's span
    readers and the routing imbalance (the device-trace readers have no
    device work to read on the host CPU)."""
    from bench.tests.test_bench_rehearsal import SPEC, bench, result
    cell = next(c for c in SPEC["workloads"] if c["name"] == CELL)
    out = result(bench(ROOT, CELL, trace=1), SPEC, cell, 1)
    assert out["correct"] is True
    assert {"moe_data_wait_ms", "moe_batch_make_s", "moe_device_wait_ms",
            "expert_load_imbalance"} <= set(out["metrics"])
    assert out["metrics"]["expert_load_imbalance"]["value"] >= 1.0


def test_control_fails_and_program_passes_at_smoke_sizes():
    """The rehearsal's own limits (the configuration's ``smoke.limits``):
    the program passes, the fp8 control and the half batch fail one at
    least (seed 5, every reading routed by the program's choices: the
    control reads 4.4e-3, 6.1e-2, 1.5e-2 and a choice gap of 0.11)."""
    import os
    import subprocess
    import sys
    p = subprocess.run(
        [sys.executable, "bench/tools/calibrate_mla_moe.py", "--workload",
         CELL, "--seeds", "5", "--faults", "--rehearse"], cwd=ROOT,
        env={**{k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    limits = smoke_files()[0]["limits"]
    rows = {r["reading"]: r for r in
            (json.loads(x) for x in p.stdout.strip().splitlines())}
    over = {name: [k for k in limits if r[k] > limits[k]]
            for name, r in rows.items()}
    assert over["program"] == [], rows["program"]
    assert over["control"], rows["control"]
    assert over["half_batch"], rows["half_batch"]
