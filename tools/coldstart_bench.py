#!/usr/bin/env python
"""Cold-start bench — restart-to-first-token: cold vs compile-cache-warm
vs AOT bundle.

Every deploy/preemption/autoscale event restarts serving processes; what
this bench measures is how long a fresh process takes from "engine
bring-up starts" to "first generated token reaches the host", under the
three restart strategies the framework ships:

* ``cold``       — nothing on disk: every program pays full XLA
  retrace + backend compile (the pre-PR-10 behavior);
* ``cache_warm`` — ``JAX_COMPILATION_CACHE_DIR`` points at a warm directory:
  compiles become disk retrievals (retrace still paid, backend compile
  skipped; the recompile watchdog labels these as cache hits);
* ``bundle``     — ``BatchDecodeEngine(bundle=…)`` loads AOT-serialized
  executables: zero retrace, zero backend compile.

Each measurement runs in a FRESH subprocess (compile caches are
per-process state; that is the whole point). ``restart_to_first_token_s``
starts AFTER model/weight construction — weights come from checkpoints in
a real deploy and cost the same in every mode — and includes engine
construction, bundle load, ``warmup()`` and the first request.
``total_wall_s`` (interpreter + imports included) is also reported.

Emits ONE final ``{"coldstart": …}`` JSON line (same contract as
serving_bench) that ``tools/perf_gate.py`` gates directly:
``coldstart.restart_to_first_token_s`` / ``coldstart.compiles`` are the
bundle path's numbers — the production restart strategy.

Usage:
    python tools/coldstart_bench.py                   # small preset
    python tools/coldstart_bench.py --preset tiny     # CI smoke
    python tools/coldstart_bench.py --modes cold,bundle
"""

import time

_T0 = time.perf_counter()          # process-start anchor for total_wall_s

import argparse                    # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import subprocess                  # noqa: E402
import sys                         # noqa: E402
import tempfile                    # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PRESETS = {
    # vocab, hidden, intermediate, layers, heads, kv_heads, max_len
    "tiny": dict(vocab_size=128, hidden_size=64, intermediate_size=192,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, max_position_embeddings=96),
    "small": dict(vocab_size=512, hidden_size=256, intermediate_size=768,
                  num_hidden_layers=4, num_attention_heads=8,
                  num_key_value_heads=4, max_position_embeddings=512),
}


def _git_sha() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _emit(body, args):
    """Print the final ``{"coldstart": …}`` line; mirror to ``--out``.

    Same artifact contract as ``serving_bench --out``: the bench body
    plus a ``meta`` block (git sha, unix stamp, argv) in a file
    ``tools/perf_gate.py`` loads directly. Child-mode JSON lines are NOT
    artifacts — only the aggregated parent report is.
    """
    doc = {"coldstart": body}
    print(json.dumps(doc))
    if not args.out:
        return
    art = {"meta": {"bench": "coldstart_bench", "git_sha": _git_sha(),
                    "unix_time": int(time.time()),
                    "argv": sys.argv[1:]}}
    art.update(doc)
    with open(args.out, "w") as f:
        json.dump(art, f, indent=1)
        f.write("\n")
    print(f"[coldstart_bench] artifact -> {args.out}", file=sys.stderr)


def _build_model(preset: str):
    import paddlepaddle_tpu as paddle
    from paddlepaddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig(dtype="float32", **PRESETS[preset]))


def _child(args) -> int:
    """One fresh-process measurement (or bundle-priming save)."""
    from paddlepaddle_tpu.inference.decode_engine import BatchDecodeEngine
    from paddlepaddle_tpu.inference.serving import GenerationRequest
    from paddlepaddle_tpu.observability import watchdog

    bundle_path = os.path.join(args.dir, "bundle")
    model = _build_model(args.preset)
    # armed AFTER model build: weight-init compiles are outside the timed
    # window in every mode and would only add stderr noise
    watchdog.install()

    if args.child == "save":
        eng = BatchDecodeEngine(model, max_slots=4, chunk=8)
        warm = eng.warmup()
        manifest = eng.save_serving_bundle(bundle_path)
        print(json.dumps({"mode": "save",
                          "save_wall_s": manifest.get("save_wall_s"),
                          "programs": len(manifest["entries"]),
                          "warmup_wall_s": warm["wall_s"]}))
        return 0

    # measurement starts here: model/weights above are checkpoint-shaped
    # cost identical across modes, so they stay outside the timed window
    t1 = time.perf_counter()
    c0 = sum(watchdog.compile_counts().values())
    cold0 = sum(watchdog.cold_compile_counts().values())
    eng = BatchDecodeEngine(
        model, max_slots=4, chunk=8,
        bundle=bundle_path if args.child == "bundle" else None)
    if args.child == "bundle" and not (eng._bundle_info or {}).get("loaded"):
        # the engine's non-fatal fallback is right for production; for a
        # MEASUREMENT it would silently relabel the lazy path as "bundle"
        raise RuntimeError(
            f"bundle did not load ({eng._bundle_info}); refusing to "
            "publish lazy-path numbers as the bundle row")
    t_ctor = time.perf_counter()
    warm = eng.warmup()
    # the serve window: after warmup NOTHING may compile — the property
    # the compile-plan test suite pins and this bench re-confirms per mode
    serve0 = sum(watchdog.compile_counts().values())
    req = GenerationRequest(list(range(1, 25)), args.new_tokens, 0.0, 0,
                            None)
    eng.serve([req], timeout=600)
    req.result.result(5)
    t_first = req.result._t_first
    if not t_first:
        # _stamp is best-effort in the engine; for a MEASUREMENT a missing
        # TTFT stamp would silently publish restart-to-LAST-token
        raise RuntimeError("engine did not stamp first-token time; "
                           "refusing to publish a fabricated TTFT")
    from paddlepaddle_tpu.core import compile_cache

    out = {
        "mode": args.child,
        "restart_to_first_token_s": round(t_first - t1, 3),
        "engine_ctor_s": round(t_ctor - t1, 3),
        "warmup_wall_s": warm["wall_s"],
        # program_compiles: plan entries actually compiled (0 on a loaded
        # bundle — the "zero retraces" proof); compiles: every cold
        # backend compile in the window, ms-scale host-op fills included
        "program_compiles": warm["compiled"],
        "compiles": sum(watchdog.cold_compile_counts().values()) - cold0,
        "compiles_total": sum(watchdog.compile_counts().values()) - c0,
        "serve_window_compiles":
            sum(watchdog.compile_counts().values()) - serve0,
        "cache_hits": warm["cache_hits"],
        "cache": compile_cache.stats(),
        "bundle": eng._bundle_info,
        "total_wall_s": round(time.perf_counter() - _T0, 3),
    }
    print(json.dumps(out))
    return 0


def _remote_row(args, cache_env) -> dict:
    """Supervisor-spawn → first token over the wire: what a
    process-backed fleet pays per restart. Unlike the in-process rows,
    the timed window starts at SPAWN — interpreter + imports + model
    build + bundle load + socket round trip are all inside it, because a
    real restart pays all of them."""
    from paddlepaddle_tpu.inference.remote_replica import (
        RemoteReplicaClient,
        ReplicaSupervisor,
    )

    sup = ReplicaSupervisor(
        bundle=os.path.join(args.dir, "bundle"), preset=args.preset,
        name="bench", env=cache_env,
        # the save-side engine geometry: bundle programs are shape-keyed,
        # so the serving engine must match or the strict load exits 3
        engine_json=json.dumps({"max_batch_size": 4, "decode_chunk": 8,
                                "kv_page_size": 64}))
    cli = RemoteReplicaClient(supervisor=sup, name="bench")
    t1 = time.perf_counter()
    try:
        cli.start()
        t_ready = time.perf_counter()
        t_sub = time.perf_counter()
        fut = cli.submit(list(range(1, 25)),
                         max_new_tokens=args.new_tokens)
        fut.result(300)
        t_first = fut._t_first or time.perf_counter()
        info = dict(sup.ready_info)
    finally:
        sup.stop()
    row = {"mode": "remote",
           "restart_to_first_token_s": round(t_first - t1, 3),
           "spawn_to_ready_s": round(t_ready - t1, 3),
           "bundle": info.get("bundle")}
    # the window comparable to the in-process rows (their clock starts
    # AFTER model build): engine bring-up inside the replica + the first
    # request's TTFT over the wire — what the restart STRATEGY changes,
    # with the interpreter + import + model-build tax broken out
    if info.get("t_engine_ready_s") is not None:
        row["engine_to_first_token_s"] = round(
            info["t_engine_ready_s"] + (t_first - t_sub), 3)
        row["model_build_s"] = info.get("t_model_build_s")
    return row


def _run_child(args, mode: str, env_extra=None) -> dict:
    env = dict(os.environ)
    env.update(env_extra or {})
    cmd = [sys.executable, os.path.abspath(__file__), "--child", mode,
           "--dir", args.dir, "--preset", args.preset,
           "--new-tokens", str(args.new_tokens)]
    proc = subprocess.run(cmd, env=env, stdout=subprocess.PIPE, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"coldstart child {mode} exited "
                           f"{proc.returncode}")
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(f"coldstart child {mode}: no JSON line in output")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--preset", choices=sorted(PRESETS), default="small")
    ap.add_argument("--modes", default="cold,cache,bundle,bundle_cache",
                    help="comma list of cold/cache/bundle/bundle_cache/"
                    "remote (default all but remote; bundle_cache = AOT "
                    "bundle for programs + compile cache for the ms-scale "
                    "host-op stragglers — the production restart config; "
                    "remote = supervisor-spawned replica process, timed "
                    "from spawn)")
    ap.add_argument("--remote", action="store_true",
                    help="shorthand: add the remote row to --modes")
    ap.add_argument("--dir", default=None,
                    help="work dir for the bundle + compile cache "
                    "(default: a fresh temp dir)")
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="also write the final JSON to PATH as a "
                    "perf_gate-ready artifact (body + meta block with "
                    "git sha + unix stamp)")
    ap.add_argument("--child", choices=["cold", "cache", "bundle", "save"],
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.dir is None:
        args.dir = tempfile.mkdtemp(prefix="coldstart_")
    if args.child:
        return _child(args)

    modes = [m.strip() for m in args.modes.split(",") if m.strip()]
    if args.remote and "remote" not in modes:
        modes.append("remote")
    body = {"preset": args.preset, "dir": args.dir}
    if "cold" in modes:
        sys.stderr.write("[coldstart] cold restart (no artifacts)...\n")
        body["cold"] = _run_child(args, "cold")
    if "bundle" in modes:
        sys.stderr.write("[coldstart] priming: save AOT bundle...\n")
        body["bundle_save"] = _run_child(args, "save")
        sys.stderr.write("[coldstart] bundle-load restart...\n")
        body["bundle"] = _run_child(args, "bundle")
    # a within-run cache: placed from outside, the one way there is
    cache_env = {"JAX_COMPILATION_CACHE_DIR": os.path.join(args.dir,
                                                           "compile_cache")}
    cache_primed = False
    if "cache" in modes:
        sys.stderr.write("[coldstart] priming: populate compile cache...\n")
        _run_child(args, "cache", cache_env)
        cache_primed = True
        sys.stderr.write("[coldstart] cache-warm restart...\n")
        body["cache_warm"] = _run_child(args, "cache", cache_env)
    if "bundle_cache" in modes:
        if "bundle" not in modes:
            body["bundle_save"] = _run_child(args, "save")
        if not cache_primed:
            sys.stderr.write("[coldstart] priming: compile cache...\n")
            _run_child(args, "cache", cache_env)
            cache_primed = True
        sys.stderr.write("[coldstart] bundle + cache restart...\n")
        row = _run_child(args, "bundle", cache_env)
        row["mode"] = "bundle_cache"
        body["bundle_cache"] = row
    if "remote" in modes:
        if "bundle_save" not in body:
            sys.stderr.write("[coldstart] priming: save AOT bundle...\n")
            body["bundle_save"] = _run_child(args, "save")
        if not cache_primed:
            sys.stderr.write("[coldstart] priming: compile cache...\n")
            _run_child(args, "cache", cache_env)
            cache_primed = True
        sys.stderr.write("[coldstart] remote replica spawn...\n")
        body["remote"] = _remote_row(args, cache_env)

    cold = body.get("cold", {}).get("restart_to_first_token_s")
    for mode, label in (("bundle", "speedup_bundle"),
                        ("cache_warm", "speedup_cache"),
                        ("bundle_cache", "speedup_bundle_cache"),
                        ("remote", "speedup_remote")):
        cur = body.get(mode, {}).get("restart_to_first_token_s")
        if cold and cur:
            body[label] = round(cold / cur, 2)
    # headline (gated) numbers = the production restart strategy: bundle
    # if measured, else the best of what ran
    head = (body.get("bundle_cache") or body.get("bundle")
            or body.get("cache_warm") or body.get("cold"))
    if head:
        body["restart_to_first_token_s"] = head["restart_to_first_token_s"]
        body["compiles"] = head["compiles"]
    _emit(body, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
