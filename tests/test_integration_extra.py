"""Deeper integration: elasticity mid-training, burstable fleets,
HeMT-EP capacity routing, cluster-state offers."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ArchBundle, TrainConfig, get_reduced
from repro.core.capacity import BurstableNode, burstable_split
from repro.launch.cluster import ClusterState, SliceInfo
from repro.runtime.hemt_driver import HeMTTrainer, SliceSpec
from repro.runtime.train_loop import train_state_init

KEY = jax.random.PRNGKey(0)


def _tiny():
    cfg = dataclasses.replace(get_reduced("granite-3-8b"), n_layers=2)
    bundle = ArchBundle(model=cfg, train=TrainConfig(
        lr=1e-3, warmup_steps=2, total_steps=60))
    return cfg, bundle


def test_elastic_slice_loss_mid_training():
    """A slice dies mid-run; training continues on survivors, re-skewed,
    with the loss still descending (no restart, the paper's point)."""
    cfg, bundle = _tiny()
    slices3 = [SliceSpec("a", [(0.0, 1.0)], 0.02),
               SliceSpec("b", [(0.0, 0.5)], 0.02),
               SliceSpec("c", [(0.0, 1.0)], 0.02)]
    tr = HeMTTrainer(cfg, bundle, slices3, grain_batch=2, global_batch=12,
                     seq_len=16, mode="hemt", grain_cost=1.0)
    st = train_state_init(KEY, cfg, bundle)
    losses = []
    for _ in range(4):
        st, rep = tr.run_step(st)
        losses.append(rep.loss)
    # slice c is preempted
    tr.resize(slices3[:2])
    for _ in range(4):
        st, rep = tr.run_step(st)
        losses.append(rep.loss)
    assert set(rep.grain_counts) == {"a", "b"}
    assert sum(rep.grain_counts.values()) == 6      # full batch re-covered
    assert rep.grain_counts["a"] > rep.grain_counts["b"]   # still skewed
    assert np.mean(losses[-2:]) < np.mean(losses[:2])      # still learning


def test_elastic_scale_up_cold_start():
    cfg, bundle = _tiny()
    tr = HeMTTrainer(cfg, bundle, [SliceSpec("a"), SliceSpec("b", [(0.0, 0.5)])],
                     grain_batch=2, global_batch=12, seq_len=16, mode="hemt")
    st = train_state_init(KEY, cfg, bundle)
    for _ in range(3):
        st, rep = tr.run_step(st)
    # newcomer joins; cold-starts at survivor mean (paper §5.1 L_k^o rule)
    tr.resize([SliceSpec("a"), SliceSpec("b", [(0.0, 0.5)]), SliceSpec("new")])
    st, rep = tr.run_step(st)
    assert "new" in rep.grain_counts and rep.grain_counts["new"] >= 1


def test_burstable_fleet_profiles():
    """§6.2 on the trainer: slices backed by token-bucket capacity. The
    credit-rich slice keeps full speed; the depleted one runs at baseline;
    the planner converges to the burstable_split ratio."""
    cfg, bundle = _tiny()
    rich = BurstableNode(credits=1e9, baseline=0.4)    # never depletes
    poor = BurstableNode(credits=0.0, baseline=0.4)    # at baseline now
    from repro.core.simulator import SimNode
    s_rich = SimNode.burstable("rich", rich).profile
    s_poor = SimNode.burstable("poor", poor).profile
    tr = HeMTTrainer(cfg, bundle,
                     [SliceSpec("rich", s_rich, 0.02),
                      SliceSpec("poor", s_poor, 0.02)],
                     grain_batch=2, global_batch=16, seq_len=16,
                     mode="hemt", grain_cost=1.0)
    st = train_state_init(KEY, cfg, bundle)
    for _ in range(5):
        st, rep = tr.run_step(st)
    # 1.0 : 0.4 -> 6:2 grains (same as the provisioned-container case)
    assert rep.grain_counts == {"rich": 6, "poor": 2}
    # a-priori burstable plan agrees with what was learned online
    shares, _ = burstable_split([rich, poor], 8.0)
    assert shares[0] / shares[1] == pytest.approx(1.0 / 0.4, rel=0.05)


def test_hemt_ep_skew_reduces_hot_shard_tokens():
    """HeMT-EP: skewed shard capacities shift *kept* tokens away from the
    slow expert shard in the real dispatch."""
    from repro.configs.base import MoEConfig
    from repro.models.moe import expert_capacities, moe_init
    import numpy as np
    cfg_even = MoEConfig(n_experts=4, top_k=2, capacity_factor=1.0)
    cfg_skew = MoEConfig(n_experts=4, top_k=2, capacity_factor=1.0,
                         shard_capacities=(1.0, 1.0, 1.0, 0.25))
    caps_e = expert_capacities(cfg_even, 64)
    caps_s = expert_capacities(cfg_skew, 64)
    assert caps_s[3] < caps_e[3] and caps_s[:3].min() > caps_e[0] - 1
    # run dispatch and count tokens landing on expert 3
    p = moe_init(KEY, 16, 32, cfg_even, glu=True, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 16))
    from repro.models import moe as moe_mod
    out_e, _ = moe_mod.moe_apply(p, x, cfg_even)
    out_s, _ = moe_mod.moe_apply(p, x, cfg_skew)
    # outputs differ only via capacity-drop pattern; both finite
    assert np.isfinite(np.asarray(out_e)).all()
    assert np.isfinite(np.asarray(out_s)).all()
    assert not np.allclose(np.asarray(out_e), np.asarray(out_s))


def test_cluster_state_offer_report_cycle():
    """The Mesos-analogue Fig 6 loop: offers carry speed estimates; missed
    heartbeats remove slices from offers."""
    cs = ClusterState([SliceInfo("s0", 256), SliceInfo("s1", 256)],
                      heartbeat_timeout=2.0)
    cs.report("s0", grains_done=8, elapsed=1.0, now=1.0)
    cs.report("s1", grains_done=8, elapsed=2.0, now=1.0)
    offer = cs.offers()
    speeds = {s.name: s.speed for s in offer.slices}
    assert speeds["s0"] == pytest.approx(8.0)
    assert speeds["s1"] == pytest.approx(4.0)
    # s1 goes silent
    cs.report("s0", grains_done=8, elapsed=1.0, now=4.0)
    dead = cs.check()
    assert dead == ["s1"]
    assert [s.name for s in cs.offers().slices] == ["s0"]
    # revocation path
    cs.remove_slice("s1")
    cs.add_slice(SliceInfo("s2", 256, preemptible=True))
    assert "s2" in {s.name for s in cs.offers().slices}


def test_serve_cli_smoke(capsys):
    import sys
    from repro.launch import serve as serve_cli
    argv = sys.argv
    sys.argv = ["serve", "--rounds", "2", "--requests", "6", "--gen-len", "3"]
    try:
        serve_cli.main()
    finally:
        sys.argv = argv
    out = capsys.readouterr().out
    assert out.count("makespan_s") == 2


def test_train_cli_smoke(tmp_path, capsys):
    import sys
    from repro.launch import train as train_cli
    argv = sys.argv
    sys.argv = ["train", "--steps", "3", "--global-batch", "8",
                "--grain-batch", "2", "--seq-len", "16",
                "--ckpt", str(tmp_path)]
    try:
        train_cli.main()
    finally:
        sys.argv = argv
    out = capsys.readouterr().out
    assert out.count('"loss"') == 3
    # each step line carries its measured times; the last line the spans
    lines = [json.loads(x) for x in out.splitlines() if x.startswith("{")]
    assert all(0 < r["host_ms"] <= r["step_ms"] for r in lines[:3])
    spans = lines[-1]["telemetry"]["spans"]
    assert spans["repro.train.step"]["count"] >= 3
    # a checkpoint was committed and resume works
    sys.argv = ["train", "--steps", "4", "--global-batch", "8",
                "--grain-batch", "2", "--seq-len", "16",
                "--ckpt", str(tmp_path)]
    try:
        train_cli.main()
    finally:
        sys.argv = argv
    out = capsys.readouterr().out
    assert "resumed from step" in out
