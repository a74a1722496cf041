"""Serving launcher: mesh-sharded batched inference for any assigned arch.

The serving twin of launch/train.py: fits the elastic mesh, shards params
and cache by the same logical rules as the dry-run, and runs the
prefill + decode loop of serving/engine.py under that sharding.

CPU-scale usage:
  PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma_9b \
      --smoke --batch 4 --prompt-len 32 --new-tokens 32
Add --mesh --model-parallel 2 under a multi-device XLA_FLAGS env to
exercise the sharded path.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core.backend import JIT_SAFE_KINDS, MatmulBackend
from repro.launch.mesh import make_mesh_for
from repro.launch.specs import param_logical_axes, sharding_tree
from repro.models import model as M
from repro.models.frontends import make_stub_frames
from repro.models.sharding import DEFAULT_RULES, use_sharding
from repro.serving.engine import Engine, ServeConfig


def main():
    import dataclasses

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="phi4_mini_3_8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    # continuous-batching surface (ServeConfig)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode bucket width (requests resident at once)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page in the paged pool")
    ap.add_argument("--page-budget", type=int, default=0,
                    help="usable KV pages; 0 = slots * ceil(max_seq/page_size)")
    ap.add_argument("--admission", choices=["queue", "reject"], default="queue")
    ap.add_argument("--sync-interval", type=int, default=4,
                    help="decode steps between host<->device token syncs")
    ap.add_argument("--batching", choices=["continuous", "static"],
                    default="continuous",
                    help="scheduler: continuous admits mid-decode; static "
                    "gang-schedules full batches (baseline)")
    ap.add_argument("--request-timeout", type=float, default=0.0,
                    help="per-request watchdog seconds: a request still "
                    "decoding past this is evicted (reason 'timeout') and "
                    "its pages freed; 0 disables")
    ap.add_argument("--mesh", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--backend",
        # prefill/decode are jitted: only the jit-safe registered kinds.
        choices=list(JIT_SAFE_KINDS),
        default=None,
        help="matmul routing, validated against the registered kinds; "
        "'auto' turns on the autotune dispatcher for every projection",
    )
    ap.add_argument("--strassen-depth", type=int, default=1)
    ap.add_argument("--strassen-min-dim", type=int, default=1024)
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace of the run here")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.trace_out:
        from repro import obs

        obs.configure(enabled=True)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.backend:
        cfg = dataclasses.replace(
            cfg,
            matmul_backend=MatmulBackend(
                kind=args.backend,
                depth=max(args.strassen_depth, 1),
                min_dim=args.strassen_min_dim,
            ),
        )
    key = jax.random.PRNGKey(args.seed)

    mesh = None
    if args.mesh:
        mesh = make_mesh_for(jax.device_count(), args.model_parallel)
        print(f"mesh: {dict(mesh.shape)}")

    ctx = use_sharding(mesh, DEFAULT_RULES) if mesh is not None else _null()
    with ctx:
        if mesh is not None:
            p_shapes = jax.eval_shape(lambda k: M.init_params(cfg, k), key)
            p_sh = sharding_tree(p_shapes, mesh, param_logical_axes, DEFAULT_RULES)
            params = jax.jit(
                lambda k: M.init_params(cfg, k), out_shardings=p_sh
            )(key)
        else:
            params = M.init_params(cfg, key)

        # ServeConfig is the single serving-surface config; Engine applies
        # it to the model config via ServeConfig.apply_to.
        engine = Engine(
            cfg,
            params,
            ServeConfig(
                max_seq=args.max_seq,
                temperature=args.temperature,
                slots=args.slots,
                page_size=args.page_size,
                page_budget=args.page_budget,
                admission=args.admission,
                sync_interval=args.sync_interval,
                batching=args.batching,
                request_timeout_s=args.request_timeout,
            ),
        )
        prompts = jax.random.randint(
            key, (args.batch, args.prompt_len), 0, cfg.vocab
        )
        frames = (
            make_stub_frames(cfg, args.batch) if cfg.frontend == "audio_stub" else None
        )
        if cfg.is_encdec or frames is not None:
            # encoder-decoder archs serve through the legacy batched path
            t0 = time.perf_counter()
            tokens, stats = engine.generate(prompts, args.new_tokens, frames=frames)
            dt = time.perf_counter() - t0
            n = tokens.shape[0] * tokens.shape[1]
            print(
                f"arch={cfg.name} generated {tokens.shape} in {dt:.2f}s "
                f"({n/dt:.1f} tok/s incl. compile); stats={stats}"
            )
            _write_trace(args.trace_out, engine)
            return
        # request API: submit the batch as independent requests (staggered
        # lengths) and let the scheduler pack the decode bucket
        import numpy as np

        prompts_np = np.asarray(prompts)
        t0 = time.perf_counter()
        handles = [
            engine.submit(prompts_np[i], args.new_tokens + (i % 3))
            for i in range(args.batch)
        ]
        n = len(list(engine.stream(handles)))
        dt = time.perf_counter() - t0
        for h in handles:
            ttft, _ = h.latency_stats()
            print(
                f"  req {h.id}: {h.state.value} ({h.finish_reason}) "
                f"{len(h.tokens())} tokens, ttft={ttft:.3f}s"
            )
        print(
            f"arch={cfg.name} served {len(handles)} requests / {n} tokens "
            f"in {dt:.2f}s ({n/dt:.1f} tok/s incl. compile)"
        )
        print(f"serve_stats: {engine.serve_stats()}")
        _write_trace(args.trace_out, engine)


def _write_trace(path, engine):
    if not path:
        return
    from repro.obs import export

    export.write_trace(path, metrics=engine.metrics)
    obs = engine.stats()["obs"]
    print(
        f"wrote {path} ({obs['tracer']['spans']} spans, "
        f"{len(obs['metrics'])} metric series)"
    )


import contextlib


@contextlib.contextmanager
def _null():
    yield


if __name__ == "__main__":
    main()
