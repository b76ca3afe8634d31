"""Serving driver: batched prefill + decode loop on the available devices.

Greedy decoding over a batch of synthetic prompts; reports tokens/s.  The
production-mesh lowering of the same serve_step is exercised by
repro.launch.dryrun (decode_32k / long_500k cells).
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models import layers as L
from repro.models import lm
from repro.models.blocks import KV_TAIL


def serve(arch: str, *, reduced: bool = True, batch: int = 4,
          prompt_len: int = 32, gen_tokens: int = 32, seed: int = 0,
          mesh=None, greedy: bool = True):
    cfg = get_reduced(arch) if reduced else get_config(arch)
    mesh = mesh or make_test_mesh()
    # dedicated streams: reusing one key for params, prompts AND context
    # correlates weights with inputs (and makes the three draws identical
    # noise up to shape), which skews any numerics derived from them
    k_params, k_prompts, k_ctx = jax.random.split(jax.random.PRNGKey(seed), 3)
    with jax.set_mesh(mesh):
        params = lm.init_params(k_params, cfg)
        cache_len = prompt_len + gen_tokens
        prompts = jax.random.randint(k_prompts, (batch, prompt_len), 0,
                                     cfg.vocab)
        ctx = None
        if cfg.n_context_tokens or cfg.is_encdec:
            n = cfg.n_audio_frames if cfg.is_encdec else cfg.n_context_tokens
            ctx = (jax.random.normal(k_ctx, (batch, n, cfg.d_model))
                   * 0.1).astype(L.dtype_of(cfg.param_dtype))

        # inputs land on device before the clock starts, and the clock only
        # stops once the prefill actually finished: without block_until_ready
        # the async dispatch returns immediately and t_prefill measures
        # Python call overhead, not compute
        jax.block_until_ready((params, prompts, ctx))
        t0 = time.time()
        logits, caches = jax.jit(
            lambda p, t, c: lm.prefill(p, cfg, t, c))(params, prompts, ctx)
        jax.block_until_ready(logits)
        t_prefill = time.time() - t0
        caches = lm.extend_caches(caches, cfg, cache_len)

        step = jax.jit(lambda p, tok, c, pos: lm.decode_step(p, cfg, tok, c, pos))
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out_tokens = [tok]
        flush = jax.jit(lambda c: lm.flush_tails(c, cfg))
        # same discipline for the decode leg: the first-token argmax must
        # not leak into the decode timestamp
        jax.block_until_ready(tok)
        t0 = time.time()
        for i in range(gen_tokens - 1):
            logits, caches = step(params, tok, caches, jnp.asarray(prompt_len + i))
            if (i + 1) % KV_TAIL == 0:     # amortised prefix merge
                caches = flush(caches)
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
            out_tokens.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.time() - t0
        gen = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
        tok_s = batch * (gen_tokens - 1) / max(t_decode, 1e-9)
        print(f"[serve] {arch}: prefill {prompt_len} tok x{batch} in "
              f"{t_prefill*1e3:.0f} ms; decode {gen_tokens-1} steps at "
              f"{tok_s:.1f} tok/s (batch={batch})")
    return gen, tok_s


def recommend_server(roots, *, host: str = "127.0.0.1", port: int = 8177,
                     recommender=None, poll: bool = False, on_ready=None):
    """Always-on Pareto-as-a-service endpoint over campaign archives.

    GET ``/healthz`` reports index size + uptime; GET ``/metrics`` serves
    the process metrics registry in Prometheus text format (request
    counts per route, exact-vs-surrogate answer counters, fused dispatch
    count, per-request latency histogram, bad-request count); POST
    ``/recommend`` takes ``{"queries": [{...}, ...]}`` (see
    ``repro.launch.recommend.Query``) and answers the whole batch with
    all surrogate fallbacks fused into one jit dispatch, returning
    ``{"answers": [...], "dispatches": k}``.  A malformed body — invalid
    JSON, a non-object, a non-list ``queries`` — is a structured 400,
    never an empty 500.  ``poll=True`` serves a single request then
    returns (tests); ``on_ready(srv)`` fires once the socket is bound
    (``port=0`` picks an ephemeral port, readable as
    ``srv.server_port``).
    """
    import json
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from repro.launch.recommend import Query, Recommender
    from repro.obs import metrics as obs_metrics

    rec = recommender or Recommender.build(list(roots))
    # jit dispatches mutate shared trace caches; serialize query batches
    import threading
    lock = threading.Lock()
    t_started = time.time()
    reg = obs_metrics.global_registry()
    m_requests = {p: reg.counter("serve_requests_total",
                                 labels={"route": p})
                  for p in ("/healthz", "/metrics", "/recommend", "other")}
    m_bad = reg.counter("serve_bad_requests_total")
    m_exact = reg.counter("serve_answers_total",
                          labels={"source": "archive"})
    m_surrogate = reg.counter("serve_answers_total",
                              labels={"source": "surrogate"})
    m_dispatch = reg.counter("serve_fused_dispatches_total")
    m_latency = reg.histogram("serve_request_seconds")

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # quiet: stderr stays for errors
            pass

        def _reply(self, code: int, payload: dict) -> None:
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _reply_text(self, code: int, text: str) -> None:
            body = text.encode()
            self.send_response(code)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _count(self) -> None:
            m_requests.get(self.path, m_requests["other"]).inc()

        def do_GET(self):
            t0 = time.time()
            self._count()
            try:
                if self.path == "/healthz":
                    self._reply(200, {
                        "status": "ok",
                        "uptime_s": round(time.time() - t_started, 3),
                        "cells": len(rec.index.cells),
                        "candidates": len(rec.index.candidates),
                        "dispatches": rec.n_dispatches,
                        "index": {
                            "seq_len": rec.index.seq_len,
                            "batch": rec.index.batch,
                            "answered_exact": rec.n_exact,
                            "answered_surrogate": rec.n_surrogate,
                        },
                    })
                elif self.path == "/metrics":
                    self._reply_text(
                        200, obs_metrics.render_prometheus(reg.snapshot()))
                else:
                    self._reply(404, {"error": f"no route {self.path}"})
            finally:
                m_latency.observe(time.time() - t0)

        def do_POST(self):
            t0 = time.time()
            self._count()
            try:
                if self.path != "/recommend":
                    self._reply(404, {"error": f"no route {self.path}"})
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(n) or b"{}")
                    if not isinstance(req, dict):
                        raise ValueError(
                            "request body must be a JSON object, got "
                            f"{type(req).__name__}")
                    qd = req.get("queries", [])
                    if not isinstance(qd, list):
                        raise ValueError(
                            "'queries' must be a list of objects, got "
                            f"{type(qd).__name__}")
                    queries = []
                    for i, d in enumerate(qd):
                        if not isinstance(d, dict):
                            raise ValueError(
                                f"queries[{i}] must be a JSON object, "
                                f"got {type(d).__name__}")
                        queries.append(Query.from_dict(d))
                    if not queries:
                        raise ValueError("request carries no queries")
                    with lock:
                        before = rec.n_dispatches
                        answers = rec.recommend_batch(queries)
                        used = rec.n_dispatches - before
                    n_ex = sum(1 for a in answers
                               if a.source == "archive")
                    m_exact.inc(n_ex)
                    m_surrogate.inc(len(answers) - n_ex)
                    m_dispatch.inc(used)
                    self._reply(200, {
                        "answers": [a.to_dict() for a in answers],
                        "dispatches": used,
                    })
                except (ValueError, TypeError, KeyError,
                        json.JSONDecodeError) as e:
                    # malformed input is the CLIENT's 400, with a payload
                    # that says what was wrong — never a bare 500
                    m_bad.inc()
                    self._reply(400, {"error": {
                        "type": type(e).__name__, "message": str(e)}})
            finally:
                m_latency.observe(time.time() - t0)

    srv = ThreadingHTTPServer((host, port), Handler)
    print(f"[serve] recommendation server on http://{host}:{srv.server_port}"
          f" ({len(rec.index.cells)} cells, "
          f"{len(rec.index.candidates)} candidates)")
    if on_ready is not None:
        on_ready(srv)
    try:
        if poll:
            srv.handle_request()
        else:
            srv.serve_forever()
    finally:
        srv.server_close()
    return srv


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--recommend", action="append", default=[],
                    metavar="ROOT",
                    help="campaign run dir; start the recommendation "
                         "server instead of the decode loop (repeatable)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8177)
    a = ap.parse_args()
    enable_compile_cache()
    if a.recommend:
        recommend_server(a.recommend, host=a.host, port=a.port)
        return
    if not a.arch:
        ap.error("--arch is required (or pass --recommend ROOT)")
    serve(a.arch, reduced=a.reduced, batch=a.batch, prompt_len=a.prompt_len,
          gen_tokens=a.gen)


if __name__ == "__main__":
    main()
