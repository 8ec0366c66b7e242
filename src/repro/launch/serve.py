"""Serving drivers: the relational query-serving tier and the LM demo.

Relational serving (the paper's premise — queries over big matrix data as
a service): spin a ``ServeEngine`` over a synthetic catalog and serve a
zipf multi-tenant workload, printing sustained qps and p50/p99 latency
with and without cross-query CSE:

    PYTHONPATH=src python -m repro.launch.serve --relational \
        --clients 1000 --dim 48 --threads 2

LM serving (the seed scaffolding, kept): prefill a batch of prompts and
decode N tokens through the hoisted compiled steps (``repro.serve.step``
— compiled once per (cfg, shape), decode caches donated):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --batch 4 --prompt-len 64 --new-tokens 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.models import api as mapi
from repro.models.module import init_params
from repro.serve.step import compiled_decode, compiled_prefill


def serve_relational(args) -> int:
    import json

    from repro.core import Session
    from repro.obs.ledger import CostLedger
    from repro.serve import workload as wl

    rng = np.random.default_rng(args.seed)
    cost_model = None
    if args.costmodel_out or args.refit_every:
        from repro.core.calibrate import CostModel
        cost_model = CostModel(args.costmodel_out or None)
    session = Session(block_size=args.block_size, cost_model=cost_model)
    mats = wl.synthetic_catalog(session, rng, n=args.dim)
    templates = wl.query_templates(mats)
    stream = wl.client_stream(rng, templates, n_clients=args.clients,
                              n_tenants=args.tenants)
    print(f"[serve] catalog={list(mats)} templates={len(templates)} "
          f"clients={args.clients} tenants={args.tenants}")
    ledger = None
    if args.ledger_out or args.metrics_out or args.refit_every:
        # refit without an explicit output still needs the in-memory
        # rows as its fitting corpus
        ledger = CostLedger(args.ledger_out or None)
    snapshots = {}
    perf = {}
    violations = []
    for cse in (True, False):
        r = wl.run_workload(session, stream, cse=cse,
                            n_threads=args.threads,
                            tenant_max_inflight=args.tenant_inflight,
                            trace_sample=args.trace_sample,
                            ledger=ledger,
                            measure_comm=args.measure_comm,
                            refit_every=args.refit_every,
                            deadline_s=args.deadline)
        st = r["stats"]
        arm = f"cse_{'on' if cse else 'off'}"
        snapshots[arm] = st
        perf[arm] = {k: r[k] for k in ("queries", "wall_s", "qps",
                                       "p50_ms", "p99_ms", "failures",
                                       "hung", "admission_backoffs")}
        print(f"[serve] cse={'on ' if cse else 'off'} "
              f"qps={r['qps']:.0f} p50={r['p50_ms']:.2f}ms "
              f"p99={r['p99_ms']:.2f}ms root_hits={st['root_hits']} "
              f"shared_nodes={st['inter_query_cse_nodes']} "
              f"leaf_scans={st['leaf_scans']}/{st['leaf_refs']}"
              + (f" refits={st['refits']}" if args.refit_every else "")
              + (f" failures={r['failures']} hung={r['hung']} "
                 f"worker_restarts={st['worker_restarts']}"
                 if r["failures"] or r["hung"] or st["worker_crashes"]
                 else ""))
        # the chaos job's liveness gate (docs/robustness.md): every
        # admitted ticket must reach a terminal state, and the counters
        # must balance — a hung client or a lost/double-counted
        # completion is a hard failure, faults or no faults
        if st["completed"] + st["errors"] != st["submitted"]:
            violations.append(
                f"{arm}: completed({st['completed']}) + "
                f"errors({st['errors']}) != submitted({st['submitted']})")
        if r["hung"]:
            violations.append(f"{arm}: {r['hung']} ticket(s) hung past "
                              "the client timeout")
    if cost_model is not None and args.costmodel_out:
        path = cost_model.save()
        print(f"[serve] cost model v{cost_model.version} "
              f"({', '.join(cost_model.fitted_devices()) or 'unfitted'})"
              f" → {path}")
    if args.metrics_out:
        from repro.runtime import faults
        out = {"engine": snapshots, "perf": perf,
               "faults": faults.stats()}
        if ledger is not None:
            out["ledger"] = {"path": args.ledger_out,
                             "summary": ledger.summary()}
        with open(args.metrics_out, "w") as f:
            json.dump(out, f, indent=2, default=str)
        print(f"[serve] metrics → {args.metrics_out}"
              + (f", ledger → {args.ledger_out}"
                 if args.ledger_out else ""))
    if ledger is not None:
        ledger.close()
    if args.assert_complete:
        if violations:
            for v in violations:
                print(f"[serve] COMPLETENESS VIOLATION: {v}")
            return 1
        print("[serve] completeness: all tickets terminal, "
              "completed+errors == submitted in every arm")
    return 0


def serve_lm(args) -> int:
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    b, s, n_new = args.batch, args.prompt_len, args.new_tokens
    max_seq = s + n_new + (cfg.n_img_tokens if cfg.family == "vlm" else 0)

    params = init_params(jax.random.key(args.seed), mapi.spec(cfg))
    rng = np.random.default_rng(args.seed)
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, s)),
                         jnp.int32)
    batch = {"tokens": prompt}
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(b, s, cfg.d_model)), jnp.float32)
    if cfg.family == "vlm":
        batch["img_embeds"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_img_tokens, cfg.img_embed_dim)),
            jnp.float32)

    # hoisted compiled steps: a second driver run in the same process (or
    # any repro.serve.step.generate call) reuses these executables
    prefill = compiled_prefill(cfg, max_seq)
    decode = compiled_decode(cfg, donate=True)

    t0 = time.time()
    logits, caches = prefill(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    pos0 = s + (cfg.n_img_tokens if cfg.family == "vlm" else 0)
    outs = [tok]
    t0 = time.time()
    for i in range(n_new - 1):
        _, tok, caches = decode(params, caches, tok, jnp.int32(pos0 + i))
        outs.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    gen = jnp.concatenate(outs, axis=1)
    print(f"[serve] arch={cfg.arch_id} batch={b} prompt={s} new={n_new}")
    print(f"[serve] prefill {t_prefill*1e3:.0f} ms; decode "
          f"{t_decode/max(1, n_new-1)*1e3:.1f} ms/tok; "
          f"throughput {(b*(n_new-1))/max(t_decode,1e-9):.1f} tok/s")
    print(f"[serve] sample tokens: {np.asarray(gen[0, :16])}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--relational", action="store_true",
                    help="serve the relational matrix-query workload "
                         "instead of the LM demo")
    ap.add_argument("--seed", type=int, default=0)
    # relational serving
    ap.add_argument("--clients", type=int, default=1000)
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--dim", type=int, default=48)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--threads", type=int, default=2)
    ap.add_argument("--tenant-inflight", type=int, default=None,
                    help="admission: max queued+running per tenant")
    ap.add_argument("--trace-sample", type=float, default=None,
                    help="engine trace sampling rate (0..1; default: "
                         "REPRO_TRACE_SAMPLE / off)")
    ap.add_argument("--ledger-out", default=None,
                    help="append the predicted-vs-actual cost ledger "
                         "to this JSONL file")
    ap.add_argument("--metrics-out", default=None,
                    help="dump engine metric snapshots (+ ledger "
                         "summary) as JSON at exit")
    ap.add_argument("--measure-comm", action="store_true",
                    help="record measured collective bytes in ledger "
                         "rows (HLO-derived on a mesh, 0 off-mesh)")
    ap.add_argument("--refit-every", type=int, default=None,
                    help="online calibration: background-refit the "
                         "session cost model every N executed plans "
                         "from the accumulated ledger rows")
    ap.add_argument("--costmodel-out", default=None,
                    help="persist fitted cost-model coefficients "
                         "(core.calibrate) to this JSON at exit")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-ticket deadline seconds (queue wait + "
                         "execution); past it the engine finishes the "
                         "ticket with DeadlineExceeded")
    ap.add_argument("--assert-complete", action="store_true",
                    help="exit 1 unless every admitted ticket reached a "
                         "terminal state and completed+errors == "
                         "submitted (the CI chaos gate; pair with "
                         "REPRO_FAULTS=...)")
    # LM serving
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    if args.relational:
        return serve_relational(args)
    return serve_lm(args)


if __name__ == "__main__":
    raise SystemExit(main())
