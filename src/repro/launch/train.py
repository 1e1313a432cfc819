"""Production training launcher.

    python -m repro.launch.train --arch qwen3-0.6b --steps 200 \
        --mesh 1x1 --batch 8 --seq 512 --ckpt /tmp/ckpt

On a real TPU slice the mesh is (data, model) [x pod]; on the CPU container
use --mesh 1x1.  The same Trainer/step code path runs in both.
"""
import argparse
import json
import sys

import jax

from repro import configs
from repro.data.pipeline import DataConfig, synthetic_dataset
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.optim.adamw import OptimizerConfig
from repro.sharding import axis_rules, rules_for_mesh
from repro.train.trainer import Trainer, TrainerConfig


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 16x16")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--variant", default="spt", choices=["spt", "lora", "full"])
    args = ap.parse_args()
    use_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    cfg = configs.apply_variant(cfg, args.variant)
    dp, tp = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((dp, tp), ("data", "model"))
    rules = rules_for_mesh(mesh)
    ocfg = OptimizerConfig(lr=args.lr, total_steps=args.steps)
    tcfg = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt)
    data = synthetic_dataset(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                   global_batch=args.batch), steps=args.steps + 1)
    with mesh, axis_rules(rules):
        trainer = Trainer(cfg, ocfg, tcfg, mesh=mesh, rules=rules)
        report = trainer.run(data)
    print(json.dumps({"final_step": report["final_step"],
                      "last_metrics": report["metrics"][-1] if report["metrics"] else None,
                      "straggler": report["straggler"]}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
