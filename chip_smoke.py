"""Smoke run of the analyst gateway over a 100,000-vehicle fleet on a TPU.

One process drives the serving path the way `python -m repro.launch.serve
--clients 100000 --plane sharded --sessions 1` does: the fleet simulator
with its history ring (256 slots x 100k vehicles x 4 signals) resident on
the chip, one analyst session asking for gauges, platform counts,
windowed fleet statistics and the 90th percentile, then submitting one
federated round and one fused-sketch analytics window. It then checks
the results against the repo's plain references:

* every response is ``ok``, no task of the round or window failed or was
  canceled, and both closed with every online vehicle participating;
* the device sketch fold equals `sketch_reference` on 256 seeded sampled
  vehicles: counts, histograms and quantile values exactly; the largest
  ULP difference of mean and M2 is printed;
* the gateway's fleet statistics equal a numpy merge of the fold's rows;
* the round's aggregate is within 1e-6 of `aggregate_reference`;
* the fold ran the compiled Pallas kernel (``tpu_custom_call``, not
  interpret mode).

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # only the four-chip phase

With ``--chips 4`` the same mix, less the federated round, runs on a
4-chip ``clients`` mesh after a checkpoint of the warmed-up fleet is
saved there. Its statistics answers must equal, byte for byte, those of
a fresh fleet on a 1-chip mesh. The checkpoint is then restored onto one
chip and the same requests replayed: every answer must match the 4-chip
run byte for byte, and the two rings must end bit-identical.

The script exits non-zero, without its final line, when JAX finds no TPU
or any check fails. Its last line is one JSON object naming the device.
Times printed are from this one smoke run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def _ulps(a: np.ndarray, b: np.ndarray) -> int:
    """Largest distance in units in the last place between f32 arrays."""
    def ordered(x):
        i = np.asarray(x, np.float32).view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)

    return int(np.max(np.abs(ordered(a) - ordered(b)), initial=0))


def _serve_args(clients: int, seed: int, rounds: int = 1) -> argparse.Namespace:
    from repro.launch import serve

    return serve.build_parser().parse_args([
        "--clients", str(clients), "--plane", "sharded",
        "--sessions", "1", "--seed", str(seed), "--rounds", str(rounds),
    ])


# --------------------------------------------------------------------- #
# phases                                                                #
# --------------------------------------------------------------------- #
def serve_phase(sim, args, label: str):
    """The CLI's request mix on `sim`; every response and submission is
    checked. Returns (gateway, responses)."""
    from repro.launch import serve
    from repro.serve.gateway import SUBMIT_KINDS

    t0 = time.perf_counter()
    gw, responses, ticks = serve.replay_mix(sim, args)
    sim.plane.block_until_ready()
    _say(f"{label}: {len(responses)} responses over {ticks} ticks in "
         f"{time.perf_counter() - t0:.1f} s")
    for r in responses:
        _require(r.ok, f"{label}: {r.kind} answered ok=False: {r.body}")
    online = len(sim.pool.online())
    _require(online == args.clients, f"{label}: {online} vehicles online")
    for r in responses:
        if r.kind in SUBMIT_KINDS:
            b = r.body
            _say(f"{label}: {r.kind} participants={b['participants']} "
                 f"errors={b['errors']} canceled={b['canceled']} "
                 f"pumps={b['pumps']}")
            _require(b["errors"] == 0, f"{label}: {r.kind} had failed tasks")
            _require(b["canceled"] == 0, f"{label}: {r.kind} had canceled tasks")
            _require(b["participants"] == online,
                     f"{label}: {r.kind} closed with {b['participants']} of "
                     f"{online} online vehicles")
    return gw, responses


def parity_phase(gw, args, n_sample: int, seed: int) -> None:
    """Device results against the plain references, at the final tick."""
    from repro.fleet.rounds import aggregate_packed, aggregate_reference
    from repro.kernels.ops import merge_quantile_sketches
    from repro.kernels.sketch import SketchSpec, sketch_reference

    plane, spec = gw.sim.plane, SketchSpec()
    t0 = time.perf_counter()
    sk = plane.fleet_sketch(args.signal, spec)  # what the gateway reads
    rows = np.sort(np.random.default_rng(seed).choice(
        plane.n_clients, size=min(n_sample, plane.n_clients), replace=False))
    refs = [sketch_reference(plane.window(int(i), args.signal, spec.window), spec)
            for i in rows]
    for i, ref in zip(rows, refs):
        got = sk.row(int(i))
        for key in ("count", "hist", "qsk"):
            _require(got[key] == ref[key], f"vehicle {i}: {key} {got[key]} != {ref[key]}")
    ulp_mean = _ulps(sk.means[rows], [r["mean"] for r in refs])
    ulp_m2 = _ulps(sk.m2s[rows], [r["m2"] for r in refs])
    _say(f"parity: {len(rows)} sampled vehicles: counts, histograms, quantile "
         f"values exact; max ULP mean={ulp_mean} m2={ulp_m2}")

    # the gateway's statistics against a float64 numpy merge of every row
    body = gw._read_fleet_stats({"signal": args.signal})
    c = sk.counts.astype(np.float64)
    total = c.sum()
    mean = float((c * sk.means).sum() / total)
    m2 = float(sk.m2s.astype(np.float64).sum()
               + (c * (sk.means.astype(np.float64) - mean) ** 2).sum())
    _require(body["count"] == int(total), "fleet_stats count")
    _require(body["participants"] == int(np.count_nonzero(sk.counts)),
             "fleet_stats participants")
    _require(body["hist"] == [int(v) for v in sk.hists.sum(axis=0)], "fleet_stats hist")
    _require(abs(body["mean"] - mean) <= 1e-5 * abs(mean), "fleet_stats mean")
    _require(abs(body["var"] - m2 / total) <= 1e-4 * (m2 / total), "fleet_stats var")
    vals = sk.qvals.reshape(-1)
    w = np.where(np.isnan(vals), 0.0,
                 np.repeat(sk.counts.astype(np.float32) / spec.quantile_k,
                           spec.quantile_k)).astype(np.float32)
    order = np.argsort(vals, kind="stable")
    vals, cum = vals[order], np.cumsum(w[order].astype(np.float64))
    dev_qv, _ = merge_quantile_sketches(sk.qvals, sk.counts.astype(np.float32))
    _require(np.array_equal(dev_qv, vals, equal_nan=True),
             "merged quantile summary order")
    for name, q in body["quantiles"].items():
        i = min(int(np.searchsorted(cum, int(name[1:]) / 100 * cum[-1])), len(vals) - 1)
        while i > 0 and not np.isfinite(vals[i]):
            i -= 1
        _require(q == float(vals[i]), f"fleet_stats {name} {q} != {vals[i]}")
    _say(f"parity: fleet_stats over {plane.n_clients} vehicles matches a numpy "
         f"merge (count {body['count']}, mean {body['mean']!r} vs {mean!r}, "
         f"quantiles {body['quantiles']})")

    # the round's FedAvg aggregate against the per-client reference loop
    driver = gw._fed["analyst-0"]
    weights = np.asarray(driver.history[-1]["weights"], np.float32)
    dev = aggregate_packed(driver.last_msgs, weights)
    ref = aggregate_reference(driver.last_msgs, weights)
    err = float(np.max(np.abs(dev - ref)))
    _say(f"parity: FedAvg over {len(driver.last_msgs)} uploads: max |device - "
         f"aggregate_reference| = {err!r}")
    _require(err <= 1e-6, "FedAvg aggregate beyond atol 1e-6")
    _say(f"parity checks took {time.perf_counter() - t0:.1f} s")


def kernel_phase(plane, signal: str) -> None:
    """The fold the plane dispatched is the compiled Pallas kernel."""
    import jax

    from repro.kernels import sketch as sk

    spec = sk.SketchSpec()
    interpret = not sk._on_tpu()
    _require(not interpret, "sketch fold would run in interpret mode")
    # the program `sketch_ring` runs for this plane: ring gather and
    # shard_mapped kernel in one jit
    args = (plane._dhist, jax.numpy.int32(plane.t), jax.numpy.int32(plane._hist_len),
            jax.numpy.asarray(spec.edges()))
    statics = dict(mesh=plane.mesh, col=plane._col[signal], window=spec.window,
                   quantile_k=spec.quantile_k, n_bins=spec.bins, interpret=interpret)
    text = sk._ring_sketch_pallas.lower(*args, **statics).as_text()
    _require("tpu_custom_call" in text, "no tpu_custom_call in the lowered fold")
    traces = sk.fold_traces
    out = np.asarray(sk._ring_sketch_pallas(*args, **statics))[:, : plane.n_clients]
    served = plane.fleet_sketch(signal, spec)
    _require(sk.fold_traces == traces, "the plane's fold built its program again")
    out = sk.sketches_from_device(spec, out)
    for f in ("counts", "means", "m2s", "hists", "qvals"):
        _require(np.array_equal(getattr(out, f), getattr(served, f), equal_nan=True),
                 f"the kernel's {f} are not what the plane served")
    _say(f"kernel: fold lowered with tpu_custom_call, interpret={interpret}, "
         f"over a {spec.window}-slot window of a {plane._dhist.shape} ring on "
         f"{len(plane.mesh.devices.flat)} device(s); {sk.fold_traces} fold "
         f"program(s) built in this process")


def one_chip(opts) -> None:
    from repro.launch import serve

    args = _serve_args(opts.clients, opts.seed)
    t0 = time.perf_counter()
    sim = serve.build_fleet(args)
    sim.plane.block_until_ready()
    _say(f"set-up: {args.clients} vehicles built and warmed up "
         f"({args.warmup_ticks} ticks) in {time.perf_counter() - t0:.1f} s; ring "
         f"{sim.plane._dhist.shape} on {len(sim.plane._dhist.sharding.device_set)} "
         f"device(s)")
    gw, _ = serve_phase(sim, args, "1 chip")
    kernel_phase(sim.plane, args.signal)
    parity_phase(gw, args, opts.sample, opts.seed)


def four_chips(opts) -> None:
    import jax

    from repro.fleet.checkpoint import FleetCheckpoint
    from repro.launch import serve
    from repro.sharding.fleet import client_mesh

    # the FedAvg round runs on the host and one device whatever the mesh,
    # so this phase keeps the reads and the window that fold the ring
    args = _serve_args(opts.clients, opts.seed, rounds=0)
    t0 = time.perf_counter()
    sim4 = serve.build_fleet(args, mesh=client_mesh(jax.devices()[:4]))
    ring_devices = len(sim4.plane._dhist.sharding.device_set)
    _require(ring_devices == 4, f"ring spans {ring_devices} devices, not 4")
    _say(f"set-up: {args.clients} vehicles on a 4-chip mesh in "
         f"{time.perf_counter() - t0:.1f} s; ring {sim4.plane._dhist.shape} "
         f"over {ring_devices} devices")

    # checkpoint the warmed-up fleet, run on uninterrupted, then restore
    # the checkpoint onto one chip and replay the same requests there
    ckpt = ROOT / ".smoke_checkpoint"
    shutil.rmtree(ckpt, ignore_errors=True)
    t0 = time.perf_counter()
    FleetCheckpoint.save(sim4, ckpt)
    _say(f"checkpoint: saved on 4 chips in {time.perf_counter() - t0:.1f} s")
    _, r4 = serve_phase(sim4, args, "4 chips")

    # the same statistics requests on a fresh fleet on a 1-chip mesh
    one = client_mesh(jax.devices()[:1])
    reads = argparse.Namespace(**{**vars(args), "windows": 0})
    _, fresh = serve_phase(serve.build_fleet(reads, mesh=one), reads, "1 chip, fresh")
    stats4, stats1 = ([r.encode() for r in rs if r.kind in ("fleet_stats", "quantile")]
                      for rs in (r4, fresh))
    _require(len(stats4) == 2 and stats4 == stats1,
             "statistics responses differ between 4 chips and 1 chip")
    for line in stats4:
        _say(f"4 chips == 1 chip: {line.decode()}")

    t0 = time.perf_counter()
    sim1, _, _ = FleetCheckpoint.restore(ckpt, mesh=one)
    shutil.rmtree(ckpt)
    restored_devices = len(sim1.plane._dhist.sharding.device_set)
    _require(restored_devices == 1, f"restored ring on {restored_devices} devices")
    _say(f"checkpoint: restored onto 1 chip in {time.perf_counter() - t0:.1f} s")
    _, r1 = serve_phase(sim1, args, "1 chip, restored")
    _require(len(r4) == len(r1) and all(a.encode() == b.encode() for a, b in zip(r4, r1)),
             "responses differ between the 4-chip run and the restored 1-chip run")
    ring4 = np.asarray(sim4.plane._dhist)[:, : args.clients]
    ring1 = np.asarray(sim1.plane._dhist)[:, : args.clients]
    _require(sim4.t == sim1.t and np.array_equal(ring4.view(np.uint32), ring1.view(np.uint32)),
             "ring after the restored run differs from the uninterrupted run")
    _say(f"elastic restore 4 -> 1: all {len(r4)} responses byte-identical, ring "
         f"{ring4.shape} bit-identical at tick {sim4.t}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    ap.add_argument("--clients", type=int, default=100_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sample", type=int, default=256,
                    help="vehicles compared with the per-vehicle reference")
    opts = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    _require(len(devices) >= opts.chips, f"{opts.chips} chips asked, {len(devices)} found")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    _say(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    (four_chips if opts.chips == 4 else one_chip)(opts)
    _say(f"total {time.perf_counter() - t0:.1f} s")
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
