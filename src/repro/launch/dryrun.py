"""Multi-pod dry-run: lower and compile every (arch x shape) case on 512
virtual CPU devices.  A CPU-only tool: it pins ``JAX_PLATFORMS=cpu`` for
itself and the children it starts, so it never takes a chip."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# The lines above MUST run before any jax import (jax locks the platform
# and device count on first init); everything else follows.
import argparse
import json
import pathlib
import subprocess
import sys
import traceback


def main() -> int:
    ap = argparse.ArgumentParser(description="OnePiece multi-pod dry-run")
    ap.add_argument("--arch", help="architecture id (see repro.configs.ARCH_IDS)")
    ap.add_argument("--shape", help="input shape id")
    ap.add_argument("--multi-pod", action="store_true", help="2x16x16 mesh (512 chips)")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) x {single,multi} case in subprocesses")
    ap.add_argument("--out", default="experiments/dryrun", help="output dir for JSON")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--rules", default=None,
                    help="JSON dict of sharding-rule overrides (perf experiments)")
    ap.add_argument("--tag", default="", help="suffix for the output filename")
    args = ap.parse_args()

    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    if args.all:
        from repro.launch.dryrun_lib import case_list

        failures = []
        for arch, shape in case_list():
            for mp in (False, True):
                mesh_tag = "2x16x16" if mp else "16x16"
                fname = out / f"{arch}__{shape}__{mesh_tag}.json"
                if args.skip_existing and fname.exists():
                    print(f"skip {fname.name}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", str(out)]
                if mp:
                    cmd.append("--multi-pod")
                print(f"=== {arch} x {shape} x {mesh_tag}", flush=True)
                r = subprocess.run(cmd, capture_output=True, text=True)
                if r.returncode != 0:
                    failures.append((arch, shape, mesh_tag))
                    print(r.stdout[-2000:])
                    print(r.stderr[-4000:])
        print(f"done; {len(failures)} failures: {failures}")
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch and --shape required (or --all)"
    from repro.launch.dryrun_lib import run_case

    overrides = json.loads(args.rules) if args.rules else None
    stats = run_case(args.arch, args.shape, multi_pod=args.multi_pod,
                     rule_overrides=overrides)
    mesh_tag = stats["mesh"]
    tag = f"__{args.tag}" if args.tag else ""
    fname = out / f"{args.arch}__{args.shape}__{mesh_tag}{tag}.json"
    fname.write_text(json.dumps(stats, indent=2))
    m = stats["memory"]
    print(json.dumps({k: stats[k] for k in
                      ("arch", "shape", "mesh", "compile_s", "compute_s",
                       "memory_s", "collective_s", "dominant",
                       "useful_flops_ratio")}, indent=2))
    print(f"peak {m['peak_bytes']/1e9:.2f} GB/chip  fits={m['fits_hbm']}")
    print(f"wrote {fname}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
