"""Fleet chaos smoke: crash a replica mid-load, reroute, warm-replace.

The push-blocking drill for ``serving/fleet`` (docs/serving.md ·
Fleet), on the 8-device CPU sim:

1. Two gpt2-tiny replica processes come up behind the
   :class:`FleetRouter`, sharing one persistent compile-cache dir;
   the fleet ``/healthz`` must converge (every replica polled
   healthy).
2. One replica carries ``FF_FAULT_PLAN=infer_crash@K``: its (K+1)-th
   generate call hard-kills the process (``os._exit``, no drain, no
   socket close) while client load is in flight.
3. Every request the router admitted must still return 200 — the
   in-flight request on the dead replica fails over to the survivor;
   zero client-visible failures, failovers counter > 0.
4. The autoscaler (``min_replicas=2``) must notice the dead replica
   and bring a REPLACEMENT up through the shared compile cache:
   warm start asserted two ways — the cache directory gains no new
   program entries, and the replacement's ``ff_model_compiles_total``
   shows exactly the one per-process model build (flat counter +
   cache hits = warm; a cold replacement would mint new cache files).
5. Fleet ``/healthz`` converges again at 2 healthy replicas, and the
   merged ``ffstat --endpoint ... --endpoint ...`` fleet view renders
   against the live fleet (``--once``, CI-safe).
"""
import glob
import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

MODEL = "gpt2-tiny"
CRASH_AT = 2          # victim dies on its 3rd generate call
N_REQUESTS = 16
CONVERGE_S = 150.0    # CPU-sim compile budget per replica


def _post_generate(base: str, timeout_s: float = 90.0):
    body = json.dumps({
        "inputs": [{"name": "input_ids", "shape": [1, 32],
                    "datatype": "int32",
                    "data": [5, 9, 11, 13] + [0] * 28}],
        "parameters": {"prompt_len": 4, "max_new_tokens": 6,
                       "eos_token_id": 7}}).encode()
    req = urllib.request.Request(
        base + f"/v2/models/{MODEL}/generate", data=body,
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        return resp.status, json.loads(resp.read())


def _wait_converged(router, want_alive: int, deadline_s: float) -> dict:
    t_end = time.monotonic() + deadline_s
    doc = {}
    while time.monotonic() < t_end:
        doc = router.fleet_health()
        alive = sum(1 for r in doc["replicas"].values() if r["alive"])
        if doc["converged"] and alive >= want_alive:
            return doc
        time.sleep(0.5)
    raise AssertionError(
        f"fleet /healthz did not converge at {want_alive} replicas "
        f"within {deadline_s:.0f}s: {json.dumps(doc)[:500]}")


def main() -> int:
    from flexflow_tpu.serving.fleet import (Autoscaler,
                                            AutoscalerConfig,
                                            FleetRouter, serve_fleet)

    # the launcher places the replicas' compile cache, from outside
    # them: the caller's directory if there is one, else the checkout's
    from flexflow_tpu.utils.compilation_cache import CHECKOUT_CACHE_DIR
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or CHECKOUT_CACHE_DIR
    spawn_argv = [
        sys.executable, "-m", "flexflow_tpu.serving.fleet.replica",
        "--port", "{port}", "--name", "{name}", "--model", MODEL,
        "--decode-segment", "4"]
    spawn_env = {"JAX_PLATFORMS": "cpu",
                 "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                 "PYTHONPATH": REPO,
                 "JAX_COMPILATION_CACHE_DIR": cache_dir,
                 # the tiny model's programs compile in under JAX's
                 # default one-second caching threshold
                 "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
                 # replicas must NOT inherit a fault plan from the CI
                 # environment; the victim gets its own below
                 "FF_FAULT_PLAN": ""}
    router = FleetRouter(spawn_argv=spawn_argv, spawn_env=spawn_env)
    handle = serve_fleet(router)
    scaler = None
    try:
        t0 = time.monotonic()
        survivor = router.spawn(name="replica-a")
        victim = router.spawn(
            name="replica-b",
            extra_env={"FF_FAULT_PLAN": f"infer_crash@{CRASH_AT}"})
        _wait_converged(router, want_alive=2, deadline_s=CONVERGE_S)
        cold_ttr = max(r.ready_at - r.spawned_at
                       for r in router.replicas())
        print(f"[fleet_smoke] 2 replicas converged in "
              f"{time.monotonic() - t0:.1f}s (slowest cold "
              f"time-to-ready {cold_ttr:.1f}s)")

        # warm-start baseline: program entries minted by the cold pair
        # (forward program; decode programs appear with first traffic)
        scaler = Autoscaler(router, AutoscalerConfig(
            min_replicas=2, max_replicas=3, poll_interval_s=0.25,
            deadline_ms=60000.0, idle_polls=10 ** 6))
        scaler.start()

        # -- 2+3: crash mid-load; every admitted request succeeds ----
        statuses = []
        errors = []
        lock = threading.Lock()

        def client(k):
            try:
                st, _ = _post_generate(handle.url)
                with lock:
                    statuses.append(st)
            except urllib.error.HTTPError as e:
                with lock:
                    errors.append(f"request {k}: HTTP {e.code} "
                                  f"{e.read().decode()[:200]}")
            except Exception as e:  # noqa: BLE001 — any client-visible
                # failure fails the smoke below
                with lock:
                    errors.append(f"request {k}: {e}")

        threads = []
        for k in range(N_REQUESTS):
            t = threading.Thread(target=client, args=(k,), daemon=True)
            t.start()
            threads.append(t)
            time.sleep(0.3)  # paced load so the crash lands mid-burst
        for t in threads:
            t.join(timeout=120.0)
        assert not errors, f"client-visible failures: {errors}"
        assert len(statuses) == N_REQUESTS and \
            all(s == 200 for s in statuses), statuses
        assert victim.proc.poll() is not None, \
            "victim replica did not crash — fault plan never fired"
        st = router.fleet_health()["fleet"]
        assert st["failovers"] >= 1, \
            f"router never failed over: {st}"
        print(f"[fleet_smoke] {N_REQUESTS}/{N_REQUESTS} requests OK "
              f"across the crash (failovers={st['failovers']})")

        # decode programs are all minted now (both cold replicas +
        # post-crash traffic) — the replacement must add NOTHING
        entries_before = len(glob.glob(
            os.path.join(cache_dir, "*-cache")))

        # -- 4+5: warm replacement, fleet converges at 2 again -------
        doc = _wait_converged(router, want_alive=2,
                              deadline_s=CONVERGE_S)
        names = set(doc["replicas"])
        assert "replica-b" not in names, \
            f"dead replica still in the routable view: {names}"
        repl = next(r for r in router.replicas()
                    if r.name not in ("replica-a", "replica-b"))
        warm_ttr = repl.ready_at - repl.spawned_at
        entries_after = len(glob.glob(
            os.path.join(cache_dir, "*-cache")))
        assert entries_after <= entries_before, (
            f"replacement minted {entries_after - entries_before} new "
            f"compile-cache entries — cold start, cache not hit")
        mtext = urllib.request.urlopen(
            repl.url + "/metrics", timeout=10).read().decode()
        m = re.search(r'ff_model_compiles_total\{[^}]*model="'
                      + re.escape(MODEL) + r'"[^}]*\}\s+([0-9.]+)',
                      mtext)
        assert m and float(m.group(1)) >= 1.0, (
            "replacement's ff_model_compiles_total must witness its "
            "per-process program builds (each a cache hit — the flat "
            f"cache dir above proves warm): {m and m.group(0)}")
        acts = [a["action"] for a in scaler.actions()]
        assert "repair" in acts or "scale_up" in acts, acts
        print(f"[fleet_smoke] warm replacement {repl.name} ready in "
              f"{warm_ttr:.1f}s (cold was {cold_ttr:.1f}s); compile "
              f"cache flat at {entries_after} entries, "
              f"ff_model_compiles_total={m.group(1)}")

        # -- merged ffstat fleet view against the live fleet ---------
        eps = []
        for r in router.replicas():
            eps += ["--endpoint", r.url]
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools/ffstat.py")]
            + eps + ["--once"],
            capture_output=True, text=True, timeout=120,
            cwd=REPO)
        assert out.returncode == 0, (out.returncode, out.stderr[-500:])
        assert "ffstat fleet" in out.stdout and MODEL in out.stdout, \
            out.stdout[-500:]
        print("[fleet_smoke] merged ffstat fleet view:")
        print("\n".join("    " + ln
                        for ln in out.stdout.splitlines()[:8]))
        print("[fleet_smoke] OK")
        return 0
    finally:
        if scaler is not None:
            scaler.stop()
        handle.stop()


if __name__ == "__main__":
    sys.exit(main())
