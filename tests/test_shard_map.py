"""Elastic multi-host execution (ISSUE 9).

Three layers under test:

1. **Partition analysis** (transforms/shard_map.py): memlet
   classification — shard-local (parameter indexes the dim exactly),
   replicated (whole-read weights), collective (wcr over the partition
   -> psum) — plus *typed refusals* that leave the SDFG untouched:
   halo reads crossing the shard boundary, non-divisible extents,
   declared-replicated conflicts.
2. **Mesh-keyed compilation**: the shard count and mesh signature are
   part of the pipeline signature, so a shrunken mesh can never reuse a
   stale compiled step.
3. **Numeric equality and elastic recovery on a real multi-device
   mesh** (subprocess with ``--xla_force_host_platform_device_count``,
   since device count is fixed at jax import): the sharded compiled
   step matches the unsharded one for both training and serving; host
   death restores sharded checkpoints onto a smaller mesh with
   loss-curve-identical training and byte-identical greedy streams.

Satellite regressions ride along: HeartbeatMonitor inf-median,
FaultPlan consumed across clusters, checkpoint commit-window atomicity
and typed restore errors.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp
from repro.checkpoint import (CheckpointError, latest_step, manifest_for,
                              restore, save, save_sharded)
from repro.core.memlet import Memlet, Range, Subset
from repro.core.sdfg import SDFG
from repro.core.symbolic import sym
from repro.pipeline.passes import default_pipeline
from repro.runtime import FaultPlan, HeartbeatMonitor, SimulatedCluster
from repro.transforms.shard_map import partition_sdfg

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


# ---------------------------------------------------------------------------
# SDFG builders
# ---------------------------------------------------------------------------
def rows_sdfg(n=8, m=4, halo=False):
    """Row map: y[i] = 2 x[i] (+ optionally x[i+1]: a halo read) with a
    whole-container wcr("add") loss accumulator."""
    s = SDFG("rows")
    s.add_array("x", (n, m), "float32")
    s.add_array("y", (n, m), "float32")
    s.add_array("acc", (1,), "float32")
    st = s.add_state("main", is_start=True)
    idx = Range.index(sym("i") + 1) if halo else Range.index(sym("i"))
    st.add_mapped_tasklet(
        "rows", {"i": (0, n)},
        inputs={"xr": Memlet.simple(
            "x", Subset([idx, Range.make(0, m)]))},
        outputs={"yr": Memlet.simple(
            "y", Subset([Range.index(sym("i")), Range.make(0, m)])),
            "a": Memlet.simple("acc", wcr="add")},
        fn=lambda xr: {"yr": xr * 2.0, "a": xr.sum().reshape(1)})
    return s


def _shape0(s, name):
    return int(s.arrays[name].shape[0].evaluate({}))


# ---------------------------------------------------------------------------
# Classification
# ---------------------------------------------------------------------------
class TestClassification:
    def test_shard_local_replicated_psum(self):
        s = rows_sdfg(n=8)
        res = partition_sdfg(s, 2)
        assert res["sharded"]
        assert res["specs"]["x"] == 0 and res["specs"]["y"] == 0
        assert res["specs"]["acc"] is None
        assert "acc" in res["psum"]
        # container shapes and the map range divided in place
        assert _shape0(s, "x") == 4 and _shape0(s, "y") == 4
        assert s.metadata["shard_map"]["n_shards"] == 2
        hows = {d["container"]: d for d in res["decisions"]
                if d.get("decision") == "shard"}
        assert "indexed" in hows["x"]["how"]

    def test_weights_stay_replicated(self):
        s = rows_sdfg(n=8)
        s.add_array("w", (4, 4), "float32")  # never indexed by the map
        res = partition_sdfg(s, 2)
        assert res["sharded"]
        assert res["specs"].get("w") is None  # absent/None = replicated
        reps = [d for d in res["decisions"]
                if d.get("container") == "w"]
        assert reps and reps[0]["decision"] == "replicated"

    def test_n_shards_one_is_identity(self):
        s = rows_sdfg()
        res = partition_sdfg(s, 1)
        assert not res["sharded"] and res["decisions"] == []
        assert _shape0(s, "x") == 8

    def test_halo_read_is_typed_refusal_sdfg_untouched(self):
        s = rows_sdfg(n=8, halo=True)
        # pin y so the halo read on x is the hot parameter's violation
        s.metadata["shard_declared"] = {"y": 0}
        res = partition_sdfg(s, 2)
        assert not res["sharded"]
        refusals = [d for d in res["decisions"]
                    if d["decision"] == "shard_refused"]
        assert refusals, res["decisions"]
        assert "crosses the shard boundary" in refusals[0]["reason"]
        # validate-before-mutate: nothing divided, nothing stamped
        assert _shape0(s, "x") == 8 and _shape0(s, "y") == 8
        assert "shard_map" not in s.metadata

    def test_non_divisible_extent_refuses(self):
        s = rows_sdfg(n=6)
        res = partition_sdfg(s, 4)
        assert not res["sharded"]
        reasons = " ".join(str(d.get("reason")) for d in res["decisions"])
        assert "not divisible" in reasons
        assert _shape0(s, "x") == 6

    def test_declared_replicated_conflict_refuses(self):
        s = rows_sdfg(n=8)
        s.metadata["shard_declared"] = {"x": None, "y": 0}
        res = partition_sdfg(s, 2)
        assert not res["sharded"]
        refusals = [d for d in res["decisions"]
                    if d["decision"] == "shard_refused"]
        assert "must stay replicated" in refusals[0]["reason"]
        assert _shape0(s, "x") == 8


# ---------------------------------------------------------------------------
# Mesh-keyed compilation
# ---------------------------------------------------------------------------
class TestCacheKeys:
    def test_pipeline_signature_distinct_per_mesh(self):
        """A mesh shrink must be a cache miss: n_shards and the mesh
        signature are pipeline-signature relevant, per backend."""
        for backend in ("jnp", "pallas"):
            p0 = default_pipeline(backend)
            p2a = default_pipeline(backend, n_shards=2, mesh_sig="meshA")
            p2b = default_pipeline(backend, n_shards=2, mesh_sig="meshB")
            p4a = default_pipeline(backend, n_shards=4, mesh_sig="meshA")
            sigs = {p0.signature(), p2a.signature(), p2b.signature(),
                    p4a.signature()}
            assert len(sigs) == 4, f"{backend}: colliding signatures"

    def test_sharded_pipeline_is_named(self):
        assert default_pipeline("jnp").name == "jnp_default"
        assert default_pipeline(
            "jnp", n_shards=2, mesh_sig="m").name == "jnp_sharded"
        assert default_pipeline(
            "pallas", n_shards=2, mesh_sig="m").name == "pallas_sharded"


# ---------------------------------------------------------------------------
# Sharded checkpoints (satellite: commit window + typed restore errors)
# ---------------------------------------------------------------------------
class TestCheckpoints:
    STATE = {"params": {"w": jnp.arange(8.0).reshape(2, 4),
                        "b": jnp.ones((3,))},
             "step": jnp.asarray(5, jnp.int32)}

    def test_interrupted_save_never_shadows_a_good_checkpoint(self, tmp_path):
        """Regression: the commit used to delete the live step dir before
        moving the tmp dir in — a crash in that window left NO valid
        checkpoint. Now stale .tmp/.old dirs are invisible to
        latest_step and the committed step restores intact."""
        save(str(tmp_path), 5, self.STATE)
        (tmp_path / "step_00000009.tmp").mkdir()   # crashed mid-save
        (tmp_path / "step_00000005.old").mkdir()   # crashed mid-commit
        assert latest_step(str(tmp_path)) == 5
        got = restore(str(tmp_path), 5, self.STATE)
        np.testing.assert_array_equal(np.asarray(got["params"]["w"]),
                                      np.asarray(self.STATE["params"]["w"]))

    def test_resave_replaces_atomically(self, tmp_path):
        save(str(tmp_path), 5, self.STATE)
        newer = {"params": {"w": jnp.zeros((2, 4)), "b": jnp.ones((3,))},
                 "step": jnp.asarray(5, jnp.int32)}
        save(str(tmp_path), 5, newer)
        got = restore(str(tmp_path), 5, newer)
        assert float(np.abs(np.asarray(got["params"]["w"])).max()) == 0.0
        assert not (tmp_path / "step_00000005.old").exists()
        assert not (tmp_path / "step_00000005.tmp").exists()

    def test_restore_missing_leaf_is_typed_and_named(self, tmp_path):
        save(str(tmp_path), 5, self.STATE)
        like = {"params": {"w": self.STATE["params"]["w"],
                           "b": self.STATE["params"]["b"],
                           "extra": jnp.zeros((2,))},
                "step": self.STATE["step"]}
        with pytest.raises(CheckpointError, match="extra"):
            restore(str(tmp_path), 5, like)

    def test_sharded_manifest_records_mesh_signature(self, tmp_path):
        save_sharded(str(tmp_path), 7, self.STATE, mesh_sig="MESHSIG")
        man = manifest_for(str(tmp_path), 7)
        assert man["sharded"] is True
        assert "MESHSIG" in man["mesh_signature"]
        got = restore(str(tmp_path), 7, self.STATE)
        for a, b in zip(np.asarray(got["params"]["w"]).ravel(),
                        np.asarray(self.STATE["params"]["w"]).ravel()):
            assert a == b

    def test_restore_missing_shard_file_is_typed(self, tmp_path):
        save_sharded(str(tmp_path), 7, self.STATE)
        d = tmp_path / "step_00000007"
        victim = sorted(d.glob("leaf_*.npy"))[0]
        victim.unlink()
        with pytest.raises(CheckpointError):
            restore(str(tmp_path), 7, self.STATE)


# ---------------------------------------------------------------------------
# Satellite regressions: monitor median, fault-plan reuse
# ---------------------------------------------------------------------------
def test_heartbeat_median_survives_dead_host_inf():
    """Regression: a dead host records inf durations; those used to enter
    the straggler median, inflating the threshold to inf forever so no
    straggler was ever flagged again."""
    m = HeartbeatMonitor(deadline_s=1e9, straggler_factor=2.0)
    for _ in range(8):
        m.record(0, 1.0)
    for _ in range(16):
        assert m.record(2, float("inf")) != "straggler"
    assert m.record(0, 1.0) == "ok"
    assert m.record(1, 5.0) == "straggler"  # finite median stayed ~1.0


def test_fault_plan_reusable_across_clusters():
    """Regression: SimulatedCluster.run clears die_at_step after firing;
    sharing one plan across clusters silently dropped the fault from the
    second run. The cluster now copies the plan in __init__."""
    plan = FaultPlan(die_at_step=3, die_host=1)
    for trial in range(2):
        sim = SimulatedCluster(4, plan=plan)
        out = sim.run(6, lambda s: None, lambda s: None, lambda: 0)
        assert out["restarts"], f"trial {trial}: fault never fired"
    assert plan.die_at_step == 3


# ---------------------------------------------------------------------------
# Multi-device execution (subprocess: device count is fixed at jax import)
# ---------------------------------------------------------------------------
def _run_sub(script: str, timeout=900) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, (
        f"subprocess failed:\n{proc.stdout[-4000:]}\n{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.splitlines()[-1])


TRAIN_SCRIPT = textwrap.dedent("""
    import dataclasses, json, shutil, tempfile
    import numpy as np
    from repro.configs import get_config
    from repro.pipeline.cache import CompilationCache
    from repro.runtime import (ElasticTrainer, ElasticTrainerConfig,
                               FaultPlan, run_elastic_training)

    cfg = dataclasses.replace(get_config("starcoder2-3b").reduced(),
                              activation_dtype="float32")
    out = {}

    # sharded step == unsharded step
    t1 = ElasticTrainer(cfg, n_shards=1, seq_len=8, global_batch=4,
                        cache=CompilationCache(max_entries=8))
    t2 = ElasticTrainer(cfg, n_shards=2, seq_len=8, global_batch=4,
                        cache=CompilationCache(max_entries=8))
    s1, s2 = t1.init_state(), t2.init_state()
    diffs = []
    for step in range(2):
        s1, m1 = t1.run_step(s1, step)
        s2, m2 = t2.run_step(s2, step)
        diffs.append(abs(m1["loss"] - m2["loss"]))
    out["step_loss_maxdiff"] = max(diffs)
    rep = t2.report
    out["shard_map"] = rep.get("shard_map")
    out["n_psum"] = len(rep["shard_map"]["psum"])
    out["n_decisions"] = len([d for d in rep.get("grid_decisions", ())
                              if "shard" in str(d.get("decision"))])

    # mesh-keyed cache: k=1 and k=2 must not share an entry
    shared = CompilationCache(max_entries=8)
    ElasticTrainer(cfg, n_shards=1, seq_len=8, global_batch=4,
                   cache=shared).compiled_step()
    ElasticTrainer(cfg, n_shards=2, seq_len=8, global_batch=4,
                   cache=shared).compiled_step()
    out["cache_entries"] = shared.stats["entries"]

    # elastic: host death at step 3 -> restore sharded ckpt on smaller mesh
    d_base, d_el = tempfile.mkdtemp(), tempfile.mkdtemp()
    base = run_elastic_training(cfg, n_hosts=2, n_steps=5, ckpt_dir=d_base,
                                seq_len=8, global_batch=4,
                                checkpoint_every=2,
                                cache=CompilationCache(max_entries=8))
    el = run_elastic_training(cfg, n_hosts=2, n_steps=5, ckpt_dir=d_el,
                              plan=FaultPlan(die_at_step=3, die_host=1),
                              seq_len=8, global_batch=4, checkpoint_every=2,
                              cache=CompilationCache(max_entries=8))
    out["loss_curve_maxdiff"] = max(
        abs(base["losses"][s] - el["losses"][s]) for s in base["losses"])
    out["n_restarts"] = len(el["sim"]["restarts"])
    out["wasted_steps"] = el["sim"]["wasted_steps"]
    out["reshards"] = [(r["n_hosts"], r["n_shards"]) for r in el["reshards"]]

    # restore N -> N+1 and N -> N-1: same ckpt, different mesh, same loss
    tk2 = ElasticTrainer(cfg, n_shards=2, seq_len=8, global_batch=4,
                         tcfg=ElasticTrainerConfig(ckpt_dir=d_base),
                         cache=CompilationCache(max_entries=8))
    tk4 = ElasticTrainer(cfg, n_shards=4, seq_len=8, global_batch=4,
                         tcfg=ElasticTrainerConfig(ckpt_dir=d_base),
                         cache=CompilationCache(max_entries=8))
    tk1 = ElasticTrainer(cfg, n_shards=1, seq_len=8, global_batch=4,
                         tcfg=ElasticTrainerConfig(ckpt_dir=d_base),
                         cache=CompilationCache(max_entries=8))
    resumed = []
    for t in (tk2, tk4, tk1):
        st = t.restore_or_init()
        step = int(st["step"])
        _, m = t.run_step(st, step)
        resumed.append(m["loss"])
    out["resume_step"] = step
    out["regrow_maxdiff"] = max(abs(l - resumed[0]) for l in resumed)
    shutil.rmtree(d_base, ignore_errors=True)
    shutil.rmtree(d_el, ignore_errors=True)
    print(json.dumps(out))
""")

SERVE_SCRIPT = textwrap.dedent("""
    import dataclasses, json, os, shutil, tempfile
    import jax
    from repro.configs import get_config
    from repro.models.transformer import TransformerLM
    from repro.serving import Scheduler

    cfg = dataclasses.replace(get_config("starcoder2-3b").reduced(),
                              activation_dtype="float32")
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    PROMPTS = [[3, 1, 4], [1, 5, 9, 2], [6, 5, 3, 5, 8], [9, 7]]
    KW = dict(max_slots=4, page_size=4, n_pages=16, max_model_len=16,
              prefill_chunk=4, cache_dtype="float32", donate=False)

    def streams(sched, n_new=5):
        for pr in PROMPTS:
            sched.submit(pr, n_new)
        return {r.rid: list(r.tokens_out) for r in sched.run()}

    out = {}
    base = streams(Scheduler(model, params, **KW))
    sh = Scheduler(model, params, n_shards=2, **KW)
    got = streams(sh)
    sh.check_invariants()
    out["sharded_eq"] = got == base
    out["n_shards"] = sh.stats()["n_shards"]
    step = sh.compiler._steps[max(sh.compiler._steps)]
    sm = step.report.get("shard_map")
    out["report_sharded"] = bool(sm and sm.get("sharded"))
    out["n_decisions"] = len(step.report.get("grid_decisions", ()))
    out["rung"] = step.rung

    # snapshot -> lose host 1's shard file -> restore -> recompute
    s1 = Scheduler(model, params, n_shards=2, **KW)
    for pr in PROMPTS:
        s1.submit(pr, 5)
    for _ in range(3):
        s1.step()
    d = tempfile.mkdtemp()
    s1.snapshot_to_dir(d)
    os.remove(os.path.join(d, "host001.npz"))
    s2 = Scheduler(model, params, n_shards=2, **KW).restore_from_dir(d)
    ev = [e for e in s2.events if e["kind"] == "restore_recompute"]
    out["recompute_events"] = len(ev)
    out["recompute_kept_tokens"] = min(e["kept_tokens"] for e in ev)
    out["hostloss_eq"] = {r.rid: list(r.tokens_out)
                          for r in s2.run()} == base
    s2.check_invariants()
    out["watchdog_shard_lost"] = bool(
        s2.watchdog.faults_of("restore_shard_lost"))
    shutil.rmtree(d, ignore_errors=True)

    # live shrink 2 -> 1 mid-run: preempt-to-fit + recompiled step
    s3 = Scheduler(model, params, n_shards=2, **KW)
    for pr in PROMPTS:
        s3.submit(pr, 5)
    for _ in range(3):
        s3.step()
    sig_before = s3.stats()["mesh_signature"]
    s3.shrink(1)
    out["shrink_events"] = [e["kind"] for e in s3.events
                            if e["kind"] in ("mesh_shrink",
                                             "shrink_preempt")]
    out["mesh_sig_changed"] = s3.stats()["mesh_signature"] != sig_before
    out["shrink_eq"] = {r.rid: list(r.tokens_out)
                        for r in s3.run()} == base
    s3.check_invariants()

    # an admission's install leaves every page array in the layout the
    # compiled step gave it
    s4 = Scheduler(model, params, n_shards=2, **KW)
    s4.submit(PROMPTS[0], 5)
    s4.step()
    pages = lambda: [a for pg in (s4.pool.k_pages, s4.pool.v_pages)
                     for a in pg.values()]
    before = [a.sharding for a in pages()]
    out["pages_on_mesh"] = all(len(sh.device_set) == 2 for sh in before)
    s4.submit(PROMPTS[1], 5)
    s4._try_admit()
    out["admitted"] = sum(r is not None for r in s4.slots)
    out["admit_keeps_sharding"] = [a.sharding for a in pages()] == before
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def train_sub():
    return _run_sub(TRAIN_SCRIPT)


@pytest.fixture(scope="module")
def serve_sub():
    return _run_sub(SERVE_SCRIPT)


class TestShardedTraining:
    def test_sharded_step_matches_unsharded(self, train_sub):
        assert train_sub["step_loss_maxdiff"] < 1e-4
        assert train_sub["shard_map"]["n_shards"] == 2
        assert train_sub["n_psum"] >= 1, "wcr grads produced no psum"
        assert train_sub["n_decisions"] >= 1, \
            "no partition decisions in report['grid_decisions']"

    def test_mesh_shrink_is_cache_miss(self, train_sub):
        assert train_sub["cache_entries"] == 2

    def test_host_death_loss_curve_identical(self, train_sub):
        assert train_sub["n_restarts"] == 1
        assert train_sub["loss_curve_maxdiff"] < 1e-4
        # resharded onto fewer hosts after the death
        reshards = train_sub["reshards"]
        assert len(reshards) == 2 and reshards[1][1] < reshards[0][1]
        assert train_sub["wasted_steps"] >= 0

    def test_restore_onto_larger_and_smaller_mesh(self, train_sub):
        """One sharded checkpoint, restored N -> N-1 and N -> N+1: the
        next step's loss is identical on every mesh size."""
        assert train_sub["regrow_maxdiff"] < 1e-4
        assert train_sub["resume_step"] >= 1


class TestShardedServing:
    def test_sharded_streams_byte_identical(self, serve_sub):
        assert serve_sub["sharded_eq"]
        assert serve_sub["n_shards"] == 2
        assert serve_sub["report_sharded"]
        assert serve_sub["n_decisions"] >= 1
        assert serve_sub["rung"] in ("grid", "jit")

    def test_host_shard_loss_recomputes_token_exact(self, serve_sub):
        assert serve_sub["recompute_events"] >= 1
        assert serve_sub["recompute_kept_tokens"] > 0
        assert serve_sub["watchdog_shard_lost"]
        assert serve_sub["hostloss_eq"]

    def test_admission_keeps_page_sharding(self, serve_sub):
        assert serve_sub["pages_on_mesh"]
        assert serve_sub["admitted"] == 2
        assert serve_sub["admit_keeps_sharding"]

    def test_live_shrink_preempts_and_stays_exact(self, serve_sub):
        assert "mesh_shrink" in serve_sub["shrink_events"]
        assert "shrink_preempt" in serve_sub["shrink_events"]
        assert serve_sub["mesh_sig_changed"]
        assert serve_sub["shrink_eq"]
