#!/usr/bin/env python3
"""Does the system still start on the chip? `pio train` -> `pio deploy`, once.

Drives the main path end to end on one TPU through the entry points a user
types, at the full width of the model the repo ships: Recommendation
template, explicit ALS, rank 64, ML-20M shape (20,000,000 ratings, 137,931
users, 27,027 items; generator and ratios of bench.py's `_make_workload`,
made from --seed). Weights are whatever three sweeps give: this checks that
the program runs and computes the right thing, not how good the model is.

This process NEVER imports jax: a chip belongs to one process at a time, so
every leg that needs it runs as a child, one at a time.

  0 probe    child   platform / device_kind / count; anything but tpu stops here
  1 load     parent  `pio app new`, 20M events through write_columns (columnar)
  2 train    child   `pio train` with NO --mesh flag; the instance must record
                     platform tpu, solver pallas, device bucketing
  3 serve    child   `pio deploy --pin-model --batching`; >= 200 queries, single
                     and concurrent; tables resident on the TPU, zero non-200s,
                     zero bucket misses after warm-up; `pio undeploy`
  4 agree    parent  served top-K against a plain float32 numpy reference
                     computed here from the stored model blob
  5 twotower child   `pio train` of the Two-Tower template (dim 64, batch 8192,
                     1M interactions, 20k x 10k, bf16 GEMMs); fusedCe must be
                     "pallas" and optimizer "rows" (Adam on the rows a batch
                     gathered); loss finite and decreasing
  6 kernels  child   both Pallas kernels against their references on device;
                     the solver also at the small padded ranks spd_solve sends
                     it (the templates' default rank 10 -> K=16, 5 -> 8, 20 -> 24)

It adds no fallback of its own: no CPU default, no interpret mode, no leg
whose failure becomes a field. Any failed leg raises, the script exits
non-zero and prints no result. A passing run ends its stdout with two JSON
lines: the summary (sizes, `reduced`, facts, every leg's seconds, ...,
"claim": null), then, LAST, the verdict the chip check reads, with exactly
these keys and the device as JAX reports it:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}.
These are smoke facts on a named device, not benchmark results.

`--rehearse-cpu` runs the same plumbing at toy sizes on XLA:CPU (kernel
decisions cholesky/host/xla, Pallas in interpret mode). Every field says
platform cpu, the verdict says "ok": false, it exits 4 when every leg
passed (never 0), and it can never satisfy the chip check; it exists so the
plumbing is debugged off the chip.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import io
import json
import os
import pickle
import shutil
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

#: the contract's limit is 1200 s, compilation included; stop launching
#: work that cannot finish inside it
DEADLINE_S = 1150.0

FULL = {
    "als": {"ratings": 20_000_000, "users": 137_931, "items": 27_027,
            "rank": 64, "iterations": 3},
    "twotower": {"interactions": 1_000_000, "users": 20_000, "items": 10_000,
                 "dim": 64, "batch": 8192, "epochs": 3},
    "serve": {"sequential": 40, "concurrent": 200, "clients": 16, "num": 10},
    # "spd": [batch, rank] through spd_solve, the call the ALS sweep makes:
    # a rank that is not a multiple of 8 is padded into the next one, so
    # the templates' default rank 10 runs the kernel at K=16, rank 5 at
    # K=8 and rank 20 at K=24 — each its own Mosaic compile
    "kernels": {"chol": [[138_000, 64], [8_000, 128]],
                "spd": [[138_000, 10], [27_027, 5], [27_027, 20]],
                "ce": [8192, 64]},
}
#: engine defaults the smoke cuts in DEPTH (never width): reported in the
#: summary line's "reduced"
ENGINE_DEFAULT_DEPTH = {"als": ("iterations", "numIterations", 20),
                        "twotower": ("epochs", "epochs", 5)}
TOY = {
    "als": {"ratings": 20_000, "users": 400, "items": 150,
            "rank": 8, "iterations": 2},
    "twotower": {"interactions": 4_000, "users": 300, "items": 120,
                 "dim": 16, "batch": 256, "epochs": 2},
    "serve": {"sequential": 8, "concurrent": 24, "clients": 8, "num": 10},
    "kernels": {"chol": [[64, 16]], "spd": [[64, 10]], "ce": [256, 16]},
}

# Agreement tolerance of leg 4, per item i: |served_i - ref_i| <= AGREE_REL *
# sum_k |u_k * v_ik| + AGREE_ABS. Two float32 dot products of 64 terms can
# differ by ~2 * 64 * 2^-24 = 7.6e-6 of that sum in the worst case, so 5e-5
# leaves the float32 path a 6x margin; one bf16 MXU pass rounds both operands
# to 8 mantissa bits and lands near 2e-3 of it, 40x over. The leg also
# emulates such a pass in numpy and requires that it FAILS this bound, so the
# tolerance is shown to separate the two on the very data it judges.
AGREE_REL = 5e-5
AGREE_ABS = 1e-6

# Kernel tolerances of leg 6. SPD solver: max relative error against XLA
# Cholesky and relative residual ||Ax-b||/||b|| both under 1e-4 (float32
# factorization of a ridge-regularised SPD system; tests/test_pallas_tpu.py uses
# the same bound). Fused CE: loss within 5e-3 of the XLA reference, gradients
# within 2e-2 of the largest reference gradient entry — the kernel
# exponentiates and forms dL in bf16 (8 mantissa bits, 2^-9 ~ 2e-3 an
# element), the reference only rounds the GEMM operands.
SOLVE_TOL = 1e-4
CE_LOSS_TOL = 5e-3
CE_GRAD_TOL = 2e-2


#: exit status of a `--rehearse-cpu` run whose legs all passed (0 is a
#: pass on a TPU, 1 a failed leg, 2 no checkout beside the script)
REHEARSAL_EXIT = 4


class LegFailed(RuntimeError):
    pass


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise LegFailed(what)


# --------------------------------------------------------------------------
# children (the only code here that imports jax)
# --------------------------------------------------------------------------


def child_probe() -> int:
    import jax

    d = jax.devices()
    print(json.dumps({"platform": d[0].platform, "kind": d[0].device_kind,
                      "count": len(d)}))
    return 0


def child_kernels(spec: dict, interpret: bool) -> int:
    """Both Pallas kernels against their references, everything on device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from predictionio_tpu.ops import fused_ce, solve

    hi = jax.lax.Precision.HIGHEST
    out: dict = {"solve": [], "ce": None}

    def first_and_steady(fn):
        t0 = time.perf_counter()
        result = jax.block_until_ready(fn())
        first = time.perf_counter() - t0
        steady = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            steady.append(time.perf_counter() - t0)
        return result, first, sorted(steady)[1]

    method = "pallas_interpret" if interpret else "pallas"
    # "chol": the kernel called directly at a multiple-of-8 K; "spd": a rank
    # through spd_solve(method), which pads it into the next multiple
    for via, batch, k in (
        [("chol", *bk) for bk in spec["chol"]] + [("spd", *bk) for bk in spec["spd"]]
    ):
        @jax.jit
        def make(key, batch=batch, k=k):
            kb, kr = jax.random.split(key)
            q = jax.random.normal(kb, (batch, k, k), jnp.float32)
            a = jnp.einsum("bij,bkj->bik", q, q, precision=hi) / k
            return a + 0.1 * jnp.eye(k), jax.random.normal(kr, (batch, k))

        a, b = make(jax.random.PRNGKey(k))
        if via == "spd":
            _require(solve.pallas_rank_ok(k), f"rank {k} would not run the kernel")
            solver = jax.jit(lambda a, b: solve.spd_solve(a, b, method))
            x, first, steady = first_and_steady(lambda: solver(a, b))
        else:
            x, first, steady = first_and_steady(
                lambda: solve.chol_solve_pallas(a, b, interpret=interpret)
            )
        x_ref = solve.cholesky_solve(a, b)

        @jax.jit
        def errors(a, b, x, x_ref):
            num = jnp.max(jnp.abs(x - x_ref), axis=-1)
            den = jnp.maximum(jnp.max(jnp.abs(x_ref), axis=-1), 1e-6)
            r = jnp.einsum("bij,bj->bi", a, x, precision=hi) - b
            resid = jnp.linalg.norm(r, axis=-1) / jnp.maximum(
                jnp.linalg.norm(b, axis=-1), 1e-6
            )
            return jnp.max(num / den), jnp.max(resid)

        rel, resid = (float(v) for v in errors(a, b, x, x_ref))
        k_kernel = -(-k // 8) * 8
        rec = {
            "via": solve.SOLVE_KERNEL if via == "chol" else f"spd_solve({method})",
            "shape": [batch, k, k],
            "kernelK": k_kernel,
            "relErrVsCholesky": rel,
            "residual": resid,
            "firstCallSeconds": round(first, 2),
            "steadyMs": round(steady * 1e3, 2),
        }
        print("kernels: solve", json.dumps(rec), flush=True)
        _require(np.isfinite(rel) and rel < SOLVE_TOL,
                 f"SPD solver off Cholesky by {rel} at {rec['shape']}")
        _require(np.isfinite(resid) and resid < SOLVE_TOL,
                 f"SPD solver residual {resid} at {rec['shape']}")
        out["solve"].append(rec)
        del a, b, x, x_ref

    bsz, dim = spec["ce"]
    rng = np.random.default_rng(0)
    ue = rng.normal(size=(bsz, dim)).astype(np.float32)
    ie = rng.normal(size=(bsz, dim)).astype(np.float32)
    ue = jnp.asarray(ue / np.linalg.norm(ue, axis=1, keepdims=True))
    ie = jnp.asarray(ie / np.linalg.norm(ie, axis=1, keepdims=True))
    inv_temp = 10.0

    def reference(u, i):
        # the XLA path of ops/twotower.py's loss_fn: bf16 GEMM operands,
        # float32 accumulation, optax softmax cross-entropy both ways
        labels = jnp.arange(u.shape[0])

        def logits(a, b):
            return jnp.matmul(
                a.astype(jnp.bfloat16), b.astype(jnp.bfloat16).T,
                preferred_element_type=jnp.float32,
            ) * inv_temp

        l1 = optax.softmax_cross_entropy_with_integer_labels(logits(u, i), labels)
        l2 = optax.softmax_cross_entropy_with_integer_labels(logits(i, u), labels)
        return 0.5 * (l1.mean() + l2.mean())

    fused = jax.jit(jax.value_and_grad(
        lambda u, i: fused_ce.fused_inbatch_ce(u, i, inv_temp, interpret),
        argnums=(0, 1),
    ))
    ref = jax.jit(jax.value_and_grad(reference, argnums=(0, 1)))
    (loss, grads), first, steady = first_and_steady(lambda: fused(ue, ie))
    (loss_ref, grads_ref), _, steady_ref = first_and_steady(lambda: ref(ue, ie))
    loss, loss_ref = float(loss), float(loss_ref)
    grad_err = max(
        float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
        for g, w in zip(grads, grads_ref)
    )
    rec = {
        "shape": [bsz, dim],
        "rowsPerGridStep": fused_ce._TI,
        "loss": loss,
        "referenceLoss": loss_ref,
        "gradMaxErrOverMaxGrad": grad_err,
        "firstCallSeconds": round(first, 2),
        "steadyMs": round(steady * 1e3, 3),
        "referenceSteadyMs": round(steady_ref * 1e3, 3),
    }
    print("kernels: ce", json.dumps(rec), flush=True)
    _require(np.isfinite(loss) and abs(loss - loss_ref) < CE_LOSS_TOL * max(1.0, abs(loss_ref)),
             f"fused CE loss {loss} vs reference {loss_ref}")
    _require(np.isfinite(grad_err) and grad_err < CE_GRAD_TOL,
             f"fused CE gradients off by {grad_err} of the largest entry")
    out["ce"] = rec
    stats = jax.devices()[0].memory_stats() or {}
    out["peakBytesInUse"] = stats.get("peak_bytes_in_use")
    print(json.dumps(out))
    return 0


# --------------------------------------------------------------------------
# parent
# --------------------------------------------------------------------------


class Smoke:
    def __init__(self, sizes: dict, seed: int, rehearse: bool):
        self.sizes = sizes
        self.seed = seed
        self.rehearse = rehearse
        self.t0 = time.monotonic()
        self.base = tempfile.mkdtemp(prefix="chip_smoke_")
        self.legs: dict = {}
        self.facts: dict = {}
        self.device: dict = {}
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = HERE + os.pathsep + self.env.get("PYTHONPATH", "")
        self.server: subprocess.Popen | None = None

    # ------------------------------------------------------------ plumbing
    def remaining(self) -> float:
        left = DEADLINE_S - (time.monotonic() - self.t0)
        _require(left > 5, f"out of time: {DEADLINE_S:.0f} s budget spent")
        return left

    def say(self, msg: str) -> None:
        print(f"[{time.monotonic() - self.t0:7.1f}s] {msg}", flush=True)

    def run_child(self, name: str, argv: list) -> str:
        """Run one child to completion with the chip to itself; its output
        goes to a log shown in full on failure. Returns its stdout."""
        log = os.path.join(self.base, f"{name}.log")
        t0 = time.monotonic()
        with open(log, "w") as f:
            try:
                proc = subprocess.run(
                    [sys.executable, *argv], env=self.env, cwd=HERE,
                    stdout=subprocess.PIPE, stderr=f, text=True,
                    stdin=subprocess.DEVNULL, timeout=self.remaining(),
                )
            except subprocess.TimeoutExpired:
                raise LegFailed(f"{name}: still running at the time limit")
        self.legs[name] = {"wallSeconds": round(time.monotonic() - t0, 2)}
        if proc.returncode != 0:
            with open(log) as f:
                sys.stderr.write(f.read()[-8000:])
            sys.stderr.write(proc.stdout[-4000:])
            raise LegFailed(f"{name}: exit code {proc.returncode}")
        return proc.stdout

    def pio(self, name: str, *args: str) -> str:
        return self.run_child(
            name, ["-m", "predictionio_tpu.tools.console", *args]
        )

    # ------------------------------------------------------------ 0 probe
    def leg_probe(self) -> None:
        out = self.run_child("probe", [os.path.join(HERE, "chip_smoke.py"),
                                      "--child", "probe"])
        self.device = json.loads(out.strip().splitlines()[-1])
        self.say(f"platform: {self.device['platform']}  device_kind: "
                 f"{self.device['kind']}  count: {self.device['count']}")
        want = "cpu" if self.rehearse else "tpu"
        if self.device["platform"] != want:
            raise SystemExit(
                f"chip_smoke: JAX reports platform {self.device['platform']!r}, "
                f"need {want!r}. This script proves the system starts on a "
                "TPU; it has no CPU fallback (see --rehearse-cpu for "
                "plumbing)."
            )

    # ------------------------------------------------------------- 1 load
    def leg_load(self) -> None:
        import numpy as np

        from predictionio_tpu.data.storage import Storage
        from predictionio_tpu.tools import commands

        t0 = time.monotonic()
        p_events = Storage.get_p_events()

        def write(app: str, event: str, rows, cols, n_users, n_items, props):
            app_id = commands.app_new(app, out=lambda *_: None)[0].id
            rng = np.random.default_rng(self.seed + 9)
            return p_events.write_columns(
                app_id,
                event=event,
                entity_type="user",
                entity_codes=rows,
                entity_vocab=np.asarray([str(i) for i in range(n_users)]),
                target_entity_type="item",
                target_codes=cols,
                target_vocab=np.asarray([str(i) for i in range(n_items)]),
                event_time_us=(
                    1_600_000_000_000_000 + rng.integers(0, 10**9, rows.size)
                ).astype(np.int64),
                props=props,
            )

        # bench.py's _make_workload: uniform users, power-law items, ratings
        # 0.5..5.0 in halves
        a = self.sizes["als"]
        rng = np.random.default_rng(self.seed)
        item_p = 1.0 / np.arange(1, a["items"] + 1) ** 0.8
        item_p /= item_p.sum()
        rows = rng.integers(0, a["users"], size=a["ratings"]).astype(np.int64)
        cols = rng.choice(a["items"], size=a["ratings"], p=item_p).astype(np.int64)
        vals = rng.integers(1, 11, size=a["ratings"]).astype(np.float64) / 2.0
        t_gen = time.monotonic() - t0
        t1 = time.monotonic()
        n = write("smoke-als", "rate", rows, cols, a["users"], a["items"],
                  {"rating": vals})
        t_write = time.monotonic() - t1
        _require(n == a["ratings"], f"wrote {n} of {a['ratings']} ratings")

        t = self.sizes["twotower"]
        rng = np.random.default_rng(self.seed + 1)
        item_p = 1.0 / np.arange(1, t["items"] + 1) ** 0.8
        item_p /= item_p.sum()
        rows = rng.integers(0, t["users"], size=t["interactions"]).astype(np.int64)
        cols = rng.choice(t["items"], size=t["interactions"], p=item_p).astype(np.int64)
        n = write("smoke-tt", "view", rows, cols, t["users"], t["items"], None)
        _require(n == t["interactions"], f"wrote {n} of {t['interactions']} views")
        self.legs["load"] = {
            "wallSeconds": round(time.monotonic() - t0, 2),
            "generateSeconds": round(t_gen, 2),
            "writeColumnsSeconds": round(t_write, 2),
            "ratings": a["ratings"],
        }
        self.say(f"load: {a['ratings']:,} ratings + {t['interactions']:,} views "
                 f"in {self.legs['load']['wallSeconds']} s")

    # ------------------------------------------------------------ 2 train
    def _variant(self, name: str, doc: dict) -> str:
        path = os.path.join(self.base, f"{name}.json")
        with open(path, "w") as f:
            json.dump(doc, f)
        return path

    def _instance(self, engine_id: str):
        from predictionio_tpu.data.storage import Storage

        inst = Storage.get_meta_data_engine_instances().get_latest_completed(
            engine_id, "1", engine_id
        )
        _require(inst is not None, f"no COMPLETED instance of {engine_id}")
        return inst

    def _check_where_it_ran(self, inst, algo: str, want: dict) -> tuple:
        """The instance must say where it ran and which kernels it took —
        read from storage, without jax."""
        device = json.loads(inst.env["device"])
        kernels = json.loads(inst.env["kernels"])[algo]
        _require(device["platform"] == self.device["platform"]
                 and device["deviceKind"] == self.device["kind"],
                 f"{algo}: instance ran on {device}, probe saw {self.device}")
        _require(inst.mesh_conf == {},
                 f"{algo}: no --mesh flag on one device must train mesh-less, "
                 f"got mesh_conf {inst.mesh_conf}")
        for key, value in want.items():
            _require(kernels.get(key) == value,
                     f"{algo}: {key} is {kernels.get(key)!r}, expected "
                     f"{value!r} ({kernels})")
        return device, kernels

    def leg_train(self) -> None:
        a = self.sizes["als"]
        path = self._variant("smoke-als", {
            "id": "smoke-als", "version": "1",
            "engineFactory":
                "predictionio_tpu.templates.recommendation:engine_factory",
            "datasource": {"params": {"appName": "smoke-als"}},
            "algorithms": [{"name": "als", "params": {
                "rank": a["rank"], "numIterations": a["iterations"],
                "lambda": 0.05, "seed": self.seed,
            }}],
        })
        self.pio("train", "train", "--engine-json", path)
        self.als_variant = path
        self.als_instance = inst = self._instance("smoke-als")
        want = ({"solver": "cholesky", "bucketing": "host"} if self.rehearse
                else {"solver": "pallas", "bucketing": "device"})
        device, kernels = self._check_where_it_ran(inst, "als", want)
        sweeps = kernels["sweepSeconds"]
        _require(len(sweeps) == a["iterations"], f"ran {len(sweeps)} sweeps")
        steady = sorted(sweeps[1:])[len(sweeps[1:]) // 2]
        self.legs["train"].update({
            "phases": json.loads(inst.env["phase_timings"]),
            "bucketingSeconds": kernels["bucketingSeconds"],
            "sweepSeconds": sweeps,
            # set-up (compile and cold work) apart from steps
            "setupSeconds": round(self.legs["train"]["wallSeconds"]
                                  - steady * len(sweeps), 2),
            "steadySweepSeconds": steady,
            "peakBytesInUse": device.get("peakBytesInUse"),
        })
        self.facts["als"] = {k: kernels[k] for k in
                             ("backend", "solver", "bucketing", "precision", "rank")}
        self.say(f"train: {self.legs['train']}")

    # ------------------------------------------------------------ 3 serve
    def _get(self, port: int, path: str, timeout: float = 10.0) -> dict:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        ) as r:
            return json.loads(r.read())

    def _query(self, port: int, user: str, num: int) -> tuple:
        body = json.dumps({"user": user, "num": num}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/queries.json", data=body,
            headers={"Content-Type": "application/json"},
        )
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                status, payload = r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            status, payload = e.code, None
        return status, payload, (time.perf_counter() - t0) * 1e3

    def leg_serve(self) -> None:
        import numpy as np

        from predictionio_tpu.tools import commands

        s = self.sizes["serve"]
        a = self.sizes["als"]
        rng = np.random.default_rng(self.seed + 2)
        n_queries = s["sequential"] + s["concurrent"]
        users = [str(u) for u in rng.choice(a["users"], n_queries, replace=False)]
        with socket.socket() as sock:
            sock.bind(("127.0.0.1", 0))
            port = sock.getsockname()[1]
        log = open(os.path.join(self.base, "serve.log"), "w")
        t0 = time.monotonic()
        self.server = subprocess.Popen(
            [sys.executable, "-m", "predictionio_tpu.tools.console", "deploy",
             "--engine-json", self.als_variant, "--port", str(port),
             "--pin-model", "--batching",
             "--batch-warmup-query", json.dumps({"user": users[0], "num": s["num"]})],
            env=self.env, cwd=HERE, stdout=log, stderr=subprocess.STDOUT,
            stdin=subprocess.DEVNULL,
        )
        try:
            status = None
            while status is None:
                _require(self.server.poll() is None,
                         f"deploy exited with {self.server.returncode} before serving")
                self.remaining()
                try:
                    status = self._get(port, "/", timeout=2.0)
                except (urllib.error.URLError, ConnectionError, socket.timeout):
                    time.sleep(0.5)
            boot_s = time.monotonic() - t0
            dev = status["device"]
            _require(dev["servedFrom"] == "device"
                     and dev["platform"] == self.device["platform"]
                     and dev["deviceKind"] == self.device["kind"],
                     f"GET / says predict is served from {dev}")
            # single queries first, then concurrent ones so the batcher
            # forms real batches
            results = [self._query(port, u, s["num"])
                       for u in users[: s["sequential"]]]
            with concurrent.futures.ThreadPoolExecutor(s["clients"]) as pool:
                results += list(pool.map(
                    lambda u: self._query(port, u, s["num"]),
                    users[s["sequential"]:],
                ))
            stats = self._get(port, "/stats.json")
            commands.undeploy(port=port, out=lambda *_: None)
            self.server.wait(timeout=60)
        except Exception:
            log.flush()
            with open(log.name) as f:
                sys.stderr.write(f.read()[-8000:])
            raise
        finally:
            log.close()
        bad = [st for st, _, _ in results if st != 200]
        _require(not bad, f"{len(bad)} of {n_queries} queries were not 200: {bad[:5]}")
        batcher, cache = stats["batcher"], stats["cache"]
        _require(batcher["bucketMisses"] == 0,
                 f"{batcher['bucketMisses']} bucket misses after warm-up")
        _require(batcher["completed"] >= n_queries, f"batcher completed {batcher['completed']}")
        _require(max(int(b) for b in batcher["batchSizeHist"]) > 1,
                 f"the batcher never formed a batch: {batcher['batchSizeHist']}")
        lat_seq = sorted(ms for _, _, ms in results[: s["sequential"]])
        lat_con = sorted(ms for _, _, ms in results[s["sequential"]:])
        self.served = {u: r["itemScores"] for u, (_, r, _) in zip(users, results)}
        self.bytes_pinned = cache["bytesPinned"]
        self.legs["serve"] = {
            "wallSeconds": round(time.monotonic() - t0, 2),
            # set-up: process start, model load, pin, bucket warm-up compiles
            "bootSeconds": round(boot_s, 2),
            "warmupMs": batcher["warmupMs"],
            "queries": n_queries,
            "non200": 0,
            "bucketMisses": 0,
            "bytesPinned": cache["bytesPinned"],
            "batchSizeHist": batcher["batchSizeHist"],
            "sequentialMs": {"p50": round(lat_seq[len(lat_seq) // 2], 2),
                             "max": round(lat_seq[-1], 2)},
            "concurrentMs": {"p50": round(lat_con[len(lat_con) // 2], 2),
                             "max": round(lat_con[-1], 2)},
            "handleMs": batcher["latencyMs"]["handle"],
            "device": dev,
        }
        self.say(f"serve: {self.legs['serve']}")

    # ------------------------------------------------------------ 4 agree
    def _load_models(self, instance_id: str) -> list:
        """The stored blob, read without the classes that import jax:
        template model classes unpickle into plain attribute bags."""
        from predictionio_tpu.data.storage import Storage

        class Bag:
            pass

        class Unpickler(pickle.Unpickler):
            def find_class(self, module, name):
                if module.startswith("predictionio_tpu.templates."):
                    return Bag
                return super().find_class(module, name)

        blob = Storage.get_model_data_models().get(instance_id).models
        magic = b"PIOTPU1\x00"
        _require(blob.startswith(magic), "model blob has no PIOTPU1 magic")
        return [m for _, m in Unpickler(io.BytesIO(blob[len(magic):])).load()]

    def leg_agree(self) -> None:
        import numpy as np

        t0 = time.monotonic()
        model = self._load_models(self.als_instance.id)[0]
        U = np.asarray(model.user_factors, np.float32)
        V = np.asarray(model.item_factors, np.float32)
        a = self.sizes["als"]
        _require(U.shape[1] == a["rank"] and V.shape[1] == a["rank"],
                 f"stored factors are {U.shape} / {V.shape}")
        _require(np.isfinite(U).all() and np.isfinite(V).all(),
                 "stored factors are not finite")
        _require(self.bytes_pinned == U.nbytes + V.nbytes,
                 f"bytesPinned {self.bytes_pinned} != tables {U.nbytes + V.nbytes}")

        def bf16(x):  # round-to-nearest-even to 8 mantissa bits
            b = x.view(np.uint32)
            return ((b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000).view(np.float32)

        V16 = bf16(V)
        worst = worst_bf16 = 0.0  # in units of the per-item tolerance
        num = self.sizes["serve"]["num"]
        for user, item_scores in self.served.items():
            u = U[model.user_index[user]]
            ref = V @ u  # plain float32 numpy, full precision
            tol = AGREE_REL * (np.abs(V) @ np.abs(u)) + AGREE_ABS
            ids = np.asarray([model.item_index[s["item"]] for s in item_scores])
            got = np.asarray([s["score"] for s in item_scores], np.float32)
            _require(len(ids) == num and len(set(ids.tolist())) == num,
                     f"user {user}: served {len(ids)} items for num={num}")
            worst = max(worst, float(np.max(np.abs(got - ref[ids]) / tol[ids])))
            # ranking, beyond ties: every served item scores within tolerance
            # of the reference's k-th best, in non-increasing reference order
            kth = np.partition(ref, -num)[-num]
            _require(bool(np.all(ref[ids] >= kth - tol[ids])),
                     f"user {user}: served an item outside the reference top-{num}")
            _require(bool(np.all(np.diff(ref[ids]) <= tol[ids][1:] + tol[ids][:-1])),
                     f"user {user}: served order disagrees with the reference")
            worst_bf16 = max(worst_bf16, float(np.max(
                np.abs(V16 @ bf16(u) - ref) / tol)))
        _require(worst <= 1.0,
                 f"served scores off the float32 reference by {worst:.2f}x the tolerance")
        _require(worst_bf16 > 1.0,
                 "the tolerance would not catch a bf16-pass GEMV on this data "
                 f"({worst_bf16:.2f}x)")
        self.legs["agree"] = {
            "wallSeconds": round(time.monotonic() - t0, 2),
            "users": len(self.served),
            "precision": "float32 (HIGHEST)",
            "toleranceRel": AGREE_REL,
            "worstErrorOverTolerance": round(worst, 4),
            "bf16PassEmulationOverTolerance": round(worst_bf16, 1),
        }
        self.say(f"agree: {self.legs['agree']}")

    # --------------------------------------------------------- 5 twotower
    def leg_twotower(self) -> None:
        import numpy as np

        t = self.sizes["twotower"]
        path = self._variant("smoke-tt", {
            "id": "smoke-tt", "version": "1",
            "engineFactory": "predictionio_tpu.templates.twotower:engine_factory",
            "datasource": {"params": {"appName": "smoke-tt"}},
            "algorithms": [{"name": "twotower", "params": {
                "embeddingDim": t["dim"], "batchSize": t["batch"],
                "epochs": t["epochs"], "gemmDtype": "bfloat16",
                "seed": self.seed,
            }}],
        })
        self.pio("twotower", "train", "--engine-json", path)
        inst = self._instance("smoke-tt")
        want = {"fusedCe": "xla" if self.rehearse else "pallas",
                "optimizer": "rows",
                "gemmDtype": "bfloat16", "batch": t["batch"], "dim": t["dim"]}
        device, kernels = self._check_where_it_ran(inst, "twotower", want)
        model = self._load_models(inst.id)[0]
        losses = [loss for _, loss in model.loss_history]
        _require(len(losses) >= 2 and bool(np.isfinite(losses).all()),
                 f"two-tower losses not finite: {losses[:5]}")
        _require(losses[-1] < losses[0],
                 f"two-tower loss did not decrease: {losses[0]} -> {losses[-1]}")
        _require(np.asarray(model.user_vecs).shape == (t["users"], t["dim"])
                 and bool(np.isfinite(model.user_vecs).all())
                 and bool(np.isfinite(model.item_vecs).all()),
                 "two-tower vectors are not finite at the expected shape")
        epochs = kernels["epochSeconds"]
        steady = sorted(epochs[1:])[len(epochs[1:]) // 2]
        self.legs["twotower"].update({
            "phases": json.loads(inst.env["phase_timings"]),
            "epochSeconds": epochs,
            "stepsPerEpoch": kernels["stepsPerEpoch"],
            "setupSeconds": round(self.legs["twotower"]["wallSeconds"]
                                  - steady * len(epochs), 2),
            "steadyStepMs": round(steady / kernels["stepsPerEpoch"] * 1e3, 3),
            "lossFirst": losses[0], "lossLast": losses[-1],
            "peakBytesInUse": device.get("peakBytesInUse"),
        })
        self.facts["twotower"] = {k: kernels[k] for k in
                                  ("backend", "fusedCe", "fusedCeWhy", "optimizer",
                                   "gemmDtype")}
        self.say(f"twotower: {self.legs['twotower']}")

    # ---------------------------------------------------------- 6 kernels
    def leg_kernels(self) -> None:
        argv = [os.path.join(HERE, "chip_smoke.py"), "--child", "kernels",
                "--spec", json.dumps(self.sizes["kernels"])]
        if self.rehearse:
            argv.append("--rehearse-cpu")
        out = self.run_child("kernels", argv)
        self.legs["kernels"].update(json.loads(out.strip().splitlines()[-1]))
        self.say(f"kernels: {self.legs['kernels']}")

    # ------------------------------------------------------------- driver
    def close(self) -> None:
        if self.server is not None and self.server.poll() is None:
            self.server.kill()
            self.server.wait(timeout=30)
        shutil.rmtree(self.base, ignore_errors=True)

    def run(self) -> dict:
        # storage under the scratch dir; compile cache by the repo's one rule
        # ($JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache), shared by
        # every child so later legs reuse earlier compiles
        from predictionio_tpu.utils import compile_cache

        self.env["JAX_COMPILATION_CACHE_DIR"] = compile_cache.configure()
        for key, value in {
            "PIO_FS_BASEDIR": os.path.join(self.base, "store"),
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "COL",
            "PIO_STORAGE_SOURCES_COL_TYPE": "columnar",
            "PIO_STORAGE_SOURCES_COL_PATH": os.path.join(self.base, "events"),
        }.items():
            os.environ[key] = self.env[key] = value
        self.leg_probe()
        self.leg_load()
        self.leg_train()
        self.leg_serve()
        self.leg_agree()
        self.leg_twotower()
        self.leg_kernels()
        _require("jax" not in sys.modules, "the parent imported jax")
        return {
            # "ok" means: passed on a TPU. A rehearsal never is.
            "ok": not self.rehearse,
            **({"rehearsal": True, "legsPassed": True} if self.rehearse else {}),
            "device": self.device,
            "seed": self.seed,
            "sizes": {"als": self.sizes["als"], "twotower": self.sizes["twotower"]},
            # depth only: every width, catalog and data size is the full one
            "reduced": [
                f"{leg} {param} {self.sizes[leg][key]} of the engine default {default}"
                for leg, (key, param, default) in ENGINE_DEFAULT_DEPTH.items()
                if self.sizes[leg][key] < default
            ],
            "totalSeconds": round(time.monotonic() - self.t0, 1),
            "compileCacheDir": self.env["JAX_COMPILATION_CACHE_DIR"],
            "facts": self.facts,
            "legs": self.legs,
            "claim": None,
        }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on XLA:CPU; plumbing only, never a pass")
    ap.add_argument("--child", choices=("probe", "kernels"), help=argparse.SUPPRESS)
    ap.add_argument("--spec", help=argparse.SUPPRESS)
    args = ap.parse_args()
    try:
        import predictionio_tpu.tools.console  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: needs a checkout of the repo beside it ({e})",
              file=sys.stderr)
        return 2
    if args.child == "probe":
        return child_probe()
    if args.child == "kernels":
        return child_kernels(json.loads(args.spec), interpret=args.rehearse_cpu)
    smoke = Smoke(TOY if args.rehearse_cpu else FULL, args.seed, args.rehearse_cpu)
    try:
        result = smoke.run()
    except LegFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        smoke.close()
    print(json.dumps(result))
    # the last line is the verdict, and nothing but the verdict
    device = result["device"]
    print(json.dumps({"ok": result["ok"], "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}}))
    # a rehearsal is never a pass: its own code, for callers that read
    # only the exit status
    return REHEARSAL_EXIT if args.rehearse_cpu else 0


if __name__ == "__main__":
    sys.path.insert(0, HERE)
    sys.exit(main())
