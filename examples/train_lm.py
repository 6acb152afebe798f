"""End-to-end training driver: ~100M-parameter LM for a few hundred steps
with checkpoint/restart, using the production trainer substrate.

Run:   PYTHONPATH=src python examples/train_lm.py --steps 200
Resume: rerun the same command — it restores the latest checkpoint.

Elastic multi-host mode (ISSUE 9):

    PYTHONPATH=src python examples/train_lm.py --cluster-sim --hosts 4 \\
        --die-at 6

drives the REAL sharded compiled step (ShardMapPass over the
data-parallel gradient SDFG) through a SimulatedCluster: host 1 dies at
the given step, the latest per-host sharded checkpoint restores onto
the shrunken mesh (a compilation-cache miss recompile), and the run
asserts the loss curve is identical to an uninterrupted run.
"""
import argparse
import os
import sys

# device count is fixed at jax import: simulate the hosts before any
# repro import pulls jax in
if "--cluster-sim" in sys.argv:
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses

from repro.codegen.device import enable_compile_cache
from repro.configs.base import ModelConfig
from repro.launch.mesh import make_smoke_mesh
from repro.runtime import Trainer, TrainerConfig

# ~100M params: 12L x d=640 x ffn 2560, 10 heads, 32k vocab
LM100M = ModelConfig(
    name="lm-100m", family="dense", n_layers=12, d_model=640, n_heads=10,
    n_kv_heads=10, d_head=64, d_ff=2560, vocab=32768, tie_embeddings=True,
    activation_dtype="float32",
)


def run_cluster_sim(args):
    import shutil
    from repro.pipeline.cache import CompilationCache
    from repro.runtime import FaultPlan, run_elastic_training

    cfg = dataclasses.replace(LM100M.reduced(),
                              activation_dtype="float32")
    steps = min(args.steps, 10)
    gb, seq = 4, 16
    kw = dict(n_steps=steps, seq_len=seq, global_batch=gb,
              checkpoint_every=2)
    for d in (args.ckpt_dir + "-base", args.ckpt_dir + "-elastic"):
        shutil.rmtree(d, ignore_errors=True)
    print(f"cluster-sim: {args.hosts} hosts, host 1 dies at step "
          f"{args.die_at}, {steps} steps, batch {gb}")
    base = run_elastic_training(cfg, n_hosts=args.hosts,
                                ckpt_dir=args.ckpt_dir + "-base",
                                cache=CompilationCache(max_entries=8), **kw)
    plan = FaultPlan(die_at_step=args.die_at, die_host=1)
    el = run_elastic_training(cfg, n_hosts=args.hosts,
                              ckpt_dir=args.ckpt_dir + "-elastic",
                              plan=plan,
                              cache=CompilationCache(max_entries=8), **kw)
    sim = el["sim"]
    print("restarts:", sim["restarts"])
    print("wasted_steps:", sim["wasted_steps"])
    print("reshards:", [(r["n_hosts"], r["n_shards"])
                        for r in el["reshards"]])
    assert sim["restarts"], "the planned host death never fired"
    assert len(el["reshards"]) == 2, "no mesh shrink after the death"
    assert el["reshards"][1]["n_shards"] < el["reshards"][0]["n_shards"]
    worst = 0.0
    for step in sorted(base["losses"]):
        d = abs(base["losses"][step] - el["losses"][step])
        worst = max(worst, d)
        print(f"step {step}: base {base['losses'][step]:.6f} "
              f"elastic {el['losses'][step]:.6f} (d={d:.2e})")
    assert worst < 1e-4, (
        f"loss curve diverged after elastic recovery (max diff {worst:.2e})")
    print(f"elastic recovery is loss-curve-identical "
          f"(max diff {worst:.2e}, wasted_steps={sim['wasted_steps']})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm100m_ckpt")
    ap.add_argument("--tiny", action="store_true",
                    help="reduced model for CI-speed runs")
    ap.add_argument("--cluster-sim", action="store_true",
                    help="elastic multi-host run: sharded step + host "
                         "death + loss-curve-exact recovery")
    ap.add_argument("--hosts", type=int, default=4)
    ap.add_argument("--die-at", type=int, default=6,
                    help="cluster-sim: step at which host 1 dies")
    args = ap.parse_args()
    enable_compile_cache()

    if args.cluster_sim:
        run_cluster_sim(args)
        return

    cfg = LM100M.reduced() if args.tiny else LM100M
    print(f"model: {cfg.name} ~{cfg.n_params()/1e6:.0f}M params")
    tcfg = TrainerConfig(steps=args.steps, checkpoint_every=50,
                         ckpt_dir=args.ckpt_dir)
    trainer = Trainer(cfg, make_smoke_mesh(), tcfg, seq_len=args.seq,
                      global_batch=args.batch)

    def on_step(step, metrics):
        if step % 10 == 0:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f}")

    out = trainer.run(on_step)
    losses = [m["loss"] for m in out["log"]]
    if losses:
        print(f"first loss {losses[0]:.4f} -> last loss {losses[-1]:.4f}")
        print(f"mean step time {sum(m['s'] for m in out['log'])/len(losses):.3f}s")
    print("done; checkpoints in", args.ckpt_dir)


if __name__ == "__main__":
    main()
