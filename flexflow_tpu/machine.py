"""Machine description: TPU chips, ICI/DCN topology, mesh construction.

Re-design of the reference's ``MachineView``/``MachineResource``
(include/flexflow/machine_view.h:14,51) and the machine models used by the
simulator (include/flexflow/simulator.h:212-515). On TPU the device grid is
a named ``jax.sharding.Mesh``; a MachineView names the sub-grid an op runs
on via (start, dims, strides) for search parity, and the machine spec
carries the analytic parameters (FLOP/s, HBM BW, ICI/DCN link BW) the cost
model needs (analog of machine_config_example:1-40).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class MachineView:
    """Device sub-grid assignment of one op (machine_view.h:14).

    ``dim[i]``/``stride[i]`` enumerate device ids
    ``start_device_id + sum_i k_i * stride_i`` for ``k_i < dim[i]`` — same
    encoding as the reference so strategy files round-trip.
    """

    start_device_id: int
    dim: Tuple[int, ...]
    stride: Tuple[int, ...]

    @property
    def ndims(self) -> int:
        return len(self.dim)

    def num_parts(self) -> int:
        return math.prod(self.dim) if self.dim else 1

    def device_ids(self) -> Tuple[int, ...]:
        ids = [self.start_device_id]
        for d, s in zip(self.dim, self.stride):
            ids = [i + k * s for i in ids for k in range(d)]
        return tuple(sorted(ids))

    def hash(self) -> int:
        h = hash((self.start_device_id, self.dim, self.stride))
        return h & 0x7FFFFFFFFFFFFFFF

    @classmethod
    def single_device(cls, device_id: int = 0) -> "MachineView":
        return cls(device_id, (1,), (1,))

    @classmethod
    def all_devices(cls, num_devices: int) -> "MachineView":
        return cls(0, (num_devices,), (1,))


# Analytic chip specs for the TPU generations we model. Numbers are public
# datasheet figures (bf16 peak FLOP/s, HBM bytes/s, HBM capacity, per-link
# ICI bytes/s each direction, links per chip).
CHIP_SPECS: Dict[str, Dict[str, float]] = {
    "tpu-v4": dict(flops=275e12, hbm_bw=1.23e12, hbm_cap=32e9, ici_bw=45e9, ici_links=6),
    "tpu-v5e": dict(flops=197e12, hbm_bw=0.82e12, hbm_cap=16e9, ici_bw=45e9, ici_links=4),
    "tpu-v5p": dict(flops=459e12, hbm_bw=2.77e12, hbm_cap=95e9, ici_bw=90e9, ici_links=6),
    "tpu-v6e": dict(flops=918e12, hbm_bw=1.64e12, hbm_cap=32e9, ici_bw=90e9, ici_links=4),
    "cpu-sim": dict(flops=1e12, hbm_bw=100e9, hbm_cap=16e9, ici_bw=10e9, ici_links=4),
}


# ``jax.Device.device_kind`` (lower-cased, matched whole) -> CHIP_SPECS key.
DEVICE_KIND_TO_CHIP: Dict[str, str] = {
    "tpu v4": "tpu-v4",
    "tpu v5 lite": "tpu-v5e",
    "tpu v5e": "tpu-v5e",
    "tpu v5": "tpu-v5p",
    "tpu v5p": "tpu-v5p",
    "tpu v6 lite": "tpu-v6e",
    "tpu v6e": "tpu-v6e",
}


def _factor_torus(n: int, dims: int) -> Tuple[int, ...]:
    """Near-equal `dims`-way factorization of a slice's chip count into
    torus extents, largest first (e.g. 32 chips, 3-D -> (4, 4, 2) — the
    real v4-32 topology). Falls back to fewer dims when n doesn't split."""
    if n <= 1:
        return (n,)
    out = []
    rem = n
    for i in range(dims, 1, -1):
        target = max(1, round(rem ** (1.0 / i)))
        f = max(d for d in range(1, target + 1) if rem % d == 0)
        if f > 1:
            out.append(f)
            rem //= f
    out.append(rem)
    return tuple(sorted((x for x in out if x > 1), reverse=True)) or (n,)


@dataclasses.dataclass
class MachineSpec:
    """One slice (ICI domain) of ``num_nodes`` DCN-connected slices.

    Replaces SimpleMachineModel/EnhancedMachineModel/NetworkedMachineModel
    (simulator.h:212,229,279,515): TPU topology is a torus, so instead of an
    adjacency matrix we carry per-axis torus extents and link bandwidths.
    """

    chip: str = "tpu-v5e"
    chips_per_slice: int = 1
    num_slices: int = 1
    torus: Optional[Tuple[int, ...]] = None  # e.g. (4, 4) for v5e-16
    dcn_bw: float = 25e9  # bytes/s per slice pair
    ici_latency: float = 1e-6
    dcn_latency: float = 10e-6
    mxu_efficiency: float = 0.55  # achieved fraction of peak on real shapes
    # conv-class asymptote: convs don't reach matmul-grade MXU utilization
    # even channels-last (im2col padding, halo reads, ragged spatial
    # extents) — the search priced them at mxu_efficiency and every conv
    # cost it produced was ~5x optimistic (inception_proxy measured ~7%
    # MFU, bench_history). Calibrate from scripts/roofline.py per-class
    # aggregates; measured per-op tables still override the analytic model.
    conv_efficiency: float = 0.35
    min_op_time: float = 5e-7     # per-kernel dispatch overhead (seconds)
    # per-bucket launch cost of an async (bucketed) collective: the
    # start/done pair XLA schedules around a hidden collective still
    # costs a dispatch plus the ring's first-hop latency — the '_ovl'
    # latency-hiding pricing charges it once per bucket
    collective_launch_overhead: float = 2e-6
    # Arbitrary inter-slice fabric (the reference NetworkedMachineModel's
    # role, simulator.h:515 + network.cc ECMP routing, re-expressed
    # TPU-first): explicit slice-pair links [(i, j, bytes_per_s), ...].
    # None = uniform all-to-all at dcn_bw. Cross-slice ring collectives
    # are bottleneck-bound, so the topology reduces to an effective
    # (bandwidth, latency) for the slice ring: per consecutive pair the
    # shortest path is routed (missing direct links hop through
    # intermediate slices), the pair's bandwidth is the min link on the
    # path, and the ring's effective bandwidth is the bottleneck pair.
    dcn_links: Optional[Sequence[Tuple[int, int, float]]] = None
    # measured per-collective-kind correction factors (kind ->
    # measured/predicted ratio) from CALIBRATION.json
    # ``collective_corrections`` — the device-trace attribution's
    # calibration of these analytic ring formulas
    # (scripts/calibrate.py --ingest-drift derives them; see
    # load_collective_corrections). None/{} = uncalibrated.
    collective_corrections: Optional[Dict[str, float]] = None

    def __post_init__(self):
        if self.torus is None:
            # default per-generation ICI topology: v4/v5p slices are 3-D
            # tori, v5e/v6e are 2-D meshes. A 1-tuple means "flat /
            # unspecified" — the native model prices all axes alike then.
            dims = 3 if self.chip in ("tpu-v4", "tpu-v5p") else 2
            self.torus = _factor_torus(self.chips_per_slice, dims)
        spec = CHIP_SPECS[self.chip]
        self.flops = spec["flops"]
        self.hbm_bw = spec["hbm_bw"]
        self.hbm_cap = spec["hbm_cap"]
        self.ici_bw = spec["ici_bw"]

    # keys a --machine-model-file may set, with unit conversions from the
    # reference's GB/s + ms conventions where they map
    _FILE_KEYS = {
        "chip": ("chip", str),
        "chips_per_slice": ("chips_per_slice", int),
        "num_slices": ("num_slices", int),
        "flops": ("flops", float),
        "hbm_bw": ("hbm_bw", float),
        "hbm_cap": ("hbm_cap", float),
        "ici_bw": ("ici_bw", float),
        "ici_latency": ("ici_latency", float),
        "dcn_bw": ("dcn_bw", float),
        "dcn_latency": ("dcn_latency", float),
        "mxu_efficiency": ("mxu_efficiency", float),
        "conv_efficiency": ("conv_efficiency", float),
        "min_op_time": ("min_op_time", float),
        "collective_launch_overhead": ("collective_launch_overhead", float),
        # per-slice ICI torus extents: JSON list or "4 2" in key=value form
        "torus": ("torus",
                  lambda v: tuple(int(x) for x in
                                  (v.split() if isinstance(v, str) else v))),
        # reference machine_config_example vocabulary (GB/s, ms):
        # nodes = DCN domains; nvlink = intra-node device link -> ICI;
        # nic = inter-node link -> DCN
        "num_nodes": ("num_slices", int),
        "nvlink_bandwidth": ("ici_bw", lambda v: float(v) * 1e9),
        "nvlink_latency": ("ici_latency", lambda v: float(v) * 1e-3),
        "nic_bandwidth": ("dcn_bw", lambda v: float(v) * 1e9),
        "nic_latency": ("dcn_latency", lambda v: float(v) * 1e-3),
        # arbitrary inter-slice fabric: [[i, j, bytes_per_s], ...]
        # (NetworkedMachineModel's adjacency-matrix role, simulator.h:515)
        "dcn_links": ("dcn_links",
                      lambda v: [(int(i), int(j), float(bw))
                                 for i, j, bw in v]),
    }

    @classmethod
    def from_file(cls, path: str) -> "MachineSpec":
        """Parse a --machine-model-file: JSON with this class's field
        names, or the reference's ``key = value`` format
        (machine_config_example) with its GPU-era keys mapped onto the
        TPU model (nvlink→ICI, nic→DCN, num_nodes→slices). Unknown keys
        are ignored, as the reference's parser does."""
        import json as _json

        with open(path) as f:
            text = f.read()
        values: Dict[str, object] = {}
        try:
            data = _json.loads(text)
            if isinstance(data, dict):
                values = data
        except ValueError:
            for line in text.splitlines():
                line = line.split("#", 1)[0].strip()
                if "=" not in line:
                    continue
                k, v = (s.strip() for s in line.split("=", 1))
                if k == "dcn_link":
                    # repeatable: "dcn_link = i j bytes_per_s"
                    i, j, bw = v.split()
                    values.setdefault("dcn_links", []).append(
                        [int(i), int(j), float(bw)])
                else:
                    values[k] = v
        init = {}
        overrides = {}
        field_names = {f.name for f in dataclasses.fields(cls)}
        for key, raw in values.items():
            mapped = cls._FILE_KEYS.get(key)
            if mapped is None:
                continue
            name, conv = mapped
            val = conv(raw)
            if name in field_names:
                init[name] = val
            else:
                overrides[name] = val  # flops/hbm_bw/...: post-init attrs
        spec = cls(**init)
        for name, val in overrides.items():
            setattr(spec, name, val)
        return spec

    @property
    def num_devices(self) -> int:
        return self.chips_per_slice * self.num_slices

    def effective_dcn(self) -> Tuple[float, float]:
        """(bandwidth, latency) of the cross-slice ring under the
        explicit fabric, or the uniform defaults when none is given.

        For each consecutive ring pair (i, i+1 mod S): route the
        shortest path over the link graph (ECMP-role reduction:
        hop-count shortest, bottleneck bandwidth); the ring is paced by
        its slowest pair, and latency scales with the longest routed
        path. Unreachable pairs fall back to the uniform dcn_bw with a
        2-hop penalty (the fabric must be connected through a spine)."""
        if not self.dcn_links or self.num_slices <= 1:
            return self.dcn_bw, self.dcn_latency
        S = self.num_slices
        adj: Dict[int, Dict[int, float]] = {i: {} for i in range(S)}
        for i, j, bw in self.dcn_links:
            i, j, bw = int(i), int(j), float(bw)
            if i == j or i >= S or j >= S:
                continue
            adj[i][j] = max(adj[i].get(j, 0.0), bw)
            adj[j][i] = max(adj[j].get(i, 0.0), bw)

        def route(a: int, b: int) -> Tuple[int, float]:
            """(hops, bottleneck bw) of the hop-shortest (then
            widest-bottleneck) a->b path — Bellman-Ford relaxation."""
            best = {a: (0, float("inf"))}
            for _ in range(S):
                changed = False
                for u, (h, bw) in list(best.items()):
                    for v, link_bw in adj[u].items():
                        cand = (h + 1, min(bw, link_bw))
                        cur = best.get(v)
                        if cur is None or cand[0] < cur[0] or (
                                cand[0] == cur[0] and cand[1] > cur[1]):
                            best[v] = cand
                            changed = True
                if not changed:
                    break
            return best.get(b, (2, self.dcn_bw))

        worst_bw = float("inf")
        worst_hops = 1
        for i in range(S):
            hops, bw = route(i, (i + 1) % S)
            worst_bw = min(worst_bw, bw)
            worst_hops = max(worst_hops, hops)
        if not np.isfinite(worst_bw):
            worst_bw = self.dcn_bw
        return worst_bw, self.dcn_latency * worst_hops

    def ici_allreduce_time(self, bytes_: int, num_chips: int) -> float:
        """Bidirectional-ring allreduce cost over ICI: 2(n-1)/n * B / bw."""
        if num_chips <= 1:
            return 0.0
        eff_bw = self.ici_bw * 2  # bidirectional links
        return self.ici_latency * (num_chips - 1) + (
            2 * (num_chips - 1) / num_chips
        ) * bytes_ / eff_bw

    def ici_allgather_time(self, bytes_out: int, num_chips: int) -> float:
        if num_chips <= 1:
            return 0.0
        eff_bw = self.ici_bw * 2
        return self.ici_latency * (num_chips - 1) + (
            (num_chips - 1) / num_chips
        ) * bytes_out / eff_bw

    def ici_alltoall_time(self, bytes_: int, num_chips: int) -> float:
        if num_chips <= 1:
            return 0.0
        return self.ici_latency + bytes_ * (num_chips - 1) / num_chips / (
            self.ici_bw * 2
        )

    def slices_spanned(self, num_chips: int) -> int:
        """How many slices a ``num_chips`` collective group crosses.
        1 = fits inside one ICI domain (pure ICI pricing)."""
        if self.num_slices <= 1 or self.chips_per_slice <= 0:
            return 1
        if num_chips <= self.chips_per_slice:
            return 1
        return min(self.num_slices,
                   -(-num_chips // self.chips_per_slice))

    def dcn_collective_time(self, kind: str, bytes_: float,
                            slices: int) -> float:
        """Ring-collective cost over the cross-slice DCN fabric:
        ``slices`` participants (one leader chip per slice), paced by
        ``effective_dcn()``'s bottleneck (bandwidth, latency)."""
        k = int(slices)
        if k <= 1:
            return 0.0
        bw, lat = self.effective_dcn()
        if kind == "all-reduce":
            return lat * (k - 1) + (2 * (k - 1) / k) * bytes_ / bw
        if kind in ("reduce-scatter", "all-gather"):
            return lat * (k - 1) + ((k - 1) / k) * bytes_ / bw
        if kind == "all-to-all":
            return lat + bytes_ * (k - 1) / k / bw
        if kind == "collective-permute":
            return lat + bytes_ / bw
        return lat * (k - 1) + (2 * (k - 1) / k) * bytes_ / bw

    def hier_collective_time(self, kind: str, bytes_: float,
                             num_chips: int) -> float:
        """Two-level decomposition of a collective whose group spans
        slices — the multislice pricing rule (native twin:
        ``hier_allreduce_time`` in ffs_machine.hpp).

        Allreduce: intra-slice reduce-scatter at ICI + cross-slice
        allreduce of the 1/chips_per_slice shard at DCN + intra-slice
        all-gather at ICI. The other kinds decompose analogously: the
        intra-slice leg runs at ICI over ``chips_per_slice`` chips and
        the cross-slice leg moves the per-slice shard over the DCN
        ring. Bytes follow ``collective_time``'s census conventions
        (per-partition payloads; reduce-scatter counts per-shard OUTPUT
        bytes)."""
        inner = min(self.chips_per_slice, num_chips)
        k = self.slices_spanned(num_chips)
        if k <= 1:
            return self.collective_time(kind, bytes_, num_chips)
        if kind == "all-reduce":
            return (self.ici_allreduce_time(bytes_, inner) / 2
                    + self.dcn_collective_time(kind, bytes_ / inner, k)
                    + self.ici_allgather_time(bytes_, inner))
        if kind == "reduce-scatter":
            full = bytes_ * num_chips  # census counted per-shard output
            return (self.ici_allreduce_time(full, inner) / 2
                    + self.dcn_collective_time(kind, full / inner, k))
        if kind == "all-gather":
            return (self.dcn_collective_time(kind, bytes_ / inner, k)
                    + self.ici_allgather_time(bytes_, inner))
        if kind == "all-to-all":
            return (self.dcn_collective_time(kind, bytes_, k)
                    + self.ici_alltoall_time(bytes_, inner))
        if kind == "collective-permute":
            # the ring wrap hop crosses slices — DCN-paced
            return self.dcn_collective_time(kind, bytes_, k)
        return (self.ici_allreduce_time(bytes_, inner) / 2
                + self.dcn_collective_time("all-reduce", bytes_ / inner, k)
                + self.ici_allgather_time(bytes_, inner))

    def collective_time(self, kind: str, bytes_: float,
                        num_chips: int) -> float:
        """Analytic time for ``bytes_`` moved by one HLO collective kind
        (the census vocabulary of flexflow_tpu/obs/inspect.py) over an
        ``num_chips`` ICI ring. Used by the drift reporter to price the
        compiled step's REAL collective census through the same machine
        model the search's simulator uses. Census bytes are
        per-partition (SPMD module), which matches these formulas'
        per-chip payload convention.

        When the group spans slices (``num_chips > chips_per_slice`` on
        a multi-slice spec) the hierarchical ICI+DCN decomposition
        prices it instead — any collective that crosses the slice
        boundary pays DCN rates for the cross-slice leg.

        When ``collective_corrections`` carries a measured factor for
        ``kind`` (device-trace attribution calibration,
        ``scripts/calibrate.py --ingest-drift``), the analytic time is
        scaled by it — the wus_rs/ag_time measured hook (ROADMAP chip
        item (a))."""
        if num_chips <= 1:
            return 0.0
        if self.slices_spanned(num_chips) > 1:
            t = self.hier_collective_time(kind, bytes_, num_chips)
            if self.collective_corrections:
                t *= self.collective_corrections.get(kind, 1.0)
            return t
        if kind == "all-reduce":
            t = self.ici_allreduce_time(bytes_, num_chips)
        elif kind == "reduce-scatter":
            # first half of XLA's large-AR decomposition: half the AR
            # ring cost of the FULL payload. The census counted the op's
            # per-shard OUTPUT bytes (1/n of the reduced buffer), so
            # scale back up before applying the AR formula.
            t = self.ici_allreduce_time(bytes_ * num_chips,
                                        num_chips) / 2
        elif kind == "all-gather":
            t = self.ici_allgather_time(bytes_, num_chips)
        elif kind == "all-to-all":
            t = self.ici_alltoall_time(bytes_, num_chips)
        elif kind == "collective-permute":
            # one neighbor hop, full payload over a bidirectional link
            t = self.ici_latency + bytes_ / (self.ici_bw * 2)
        else:
            # unknown kind: price conservatively as an allreduce
            t = self.ici_allreduce_time(bytes_, num_chips)
        if self.collective_corrections:
            t *= self.collective_corrections.get(kind, 1.0)
        return t

    def dcn_allreduce_time(self, bytes_: int) -> float:
        if self.num_slices <= 1:
            return 0.0
        n = self.num_slices
        return self.dcn_latency * (n - 1) + (2 * (n - 1) / n) * bytes_ / self.dcn_bw

    def matmul_time(self, flops: int, dtype_size: int = 2) -> float:
        # MXU peak assumed for bf16; f32 halves throughput
        peak = self.flops if dtype_size <= 2 else self.flops / 2
        return flops / peak

    def memory_time(self, bytes_: int) -> float:
        return bytes_ / self.hbm_bw


def load_collective_corrections(platform: str,
                                path: Optional[str] = None
                                ) -> Dict[str, float]:
    """Measured per-collective-kind factors (kind -> measured/predicted
    ratio) from CALIBRATION.json ``collective_corrections`` for one
    PLATFORM bucket (the jax platform string that traced them, e.g.
    "tpu"). Empty dict when the file or bucket is absent — callers
    treat that as uncalibrated."""
    import json
    import os

    if path is None:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "CALIBRATION.json")
    try:
        with open(path) as f:
            cal = json.load(f)
    except (OSError, ValueError):
        return {}
    bucket = (cal.get("collective_corrections") or {}).get(platform) or {}
    out: Dict[str, float] = {}
    for kind, e in bucket.items():
        try:
            out[kind] = float(e["factor"] if isinstance(e, dict) else e)
        except (KeyError, TypeError, ValueError):
            continue
    return out


def detect_machine_spec(num_devices: Optional[int] = None,
                        slices: int = 1) -> MachineSpec:
    """Build a MachineSpec from the live JAX backend (used at compile
    time). ``slices > 1`` splits the detected chips into that many
    DCN-connected slices (``FFConfig --slices``): chips_per_slice =
    n // slices, with the per-generation default ICI torus factored
    per SLICE rather than over the flat device count. The synthetic
    "cpu-sim" chip is chosen only on the cpu platform; any other platform
    must report a ``device_kind`` in ``DEVICE_KIND_TO_CHIP`` or this
    raises. On a real chip,
    measured per-collective calibration from CALIBRATION.json engages
    automatically (platform-gated like search/profile's op corrections;
    FFS_NO_DRIFT_CORRECTIONS opts out) — CPU runs never pick up chip
    factors or vice versa."""
    import os

    import jax

    devs = jax.devices()
    n = num_devices or len(devs)
    s = max(1, int(slices))
    if s > 1 and n % s != 0:
        raise ValueError(
            f"--slices {s} does not divide the {n} visible devices")
    platform = devs[0].platform if devs else "cpu"
    if platform == "cpu":
        chip = "cpu-sim"
    else:
        kind = devs[0].device_kind
        chip = DEVICE_KIND_TO_CHIP.get(kind.lower())
        if chip is None:
            # never a default: a synthetic peak would silently decide
            # dtype and layout and turn up in every utilization
            raise ValueError(
                f"no chip spec for device_kind {kind!r} on platform "
                f"{platform!r}: add it to DEVICE_KIND_TO_CHIP / CHIP_SPECS "
                f"in flexflow_tpu/machine.py or pass compile(machine_spec=)")
    spec = MachineSpec(chip=chip, chips_per_slice=n // s, num_slices=s)
    if platform != "cpu" and not os.environ.get("FFS_NO_DRIFT_CORRECTIONS"):
        corr = load_collective_corrections(platform)
        if corr:
            spec.collective_corrections = corr
    return spec


def make_mesh(num_devices: int, axes: Dict[str, int]):
    """Create a named ``jax.sharding.Mesh`` over the first ``num_devices``.

    ``axes`` maps axis name -> extent; product must equal num_devices.
    Canonical axis names: 'data' (sample dim), 'model' (parameter/attribute
    dims), 'seq' (sequence/context parallelism), 'expert' (MoE).
    """
    import jax
    from jax.sharding import Mesh

    sizes = tuple(axes.values())
    if math.prod(sizes) != num_devices:
        raise ValueError(f"mesh axes {axes} != {num_devices} devices")
    devs = np.array(jax.devices()[:num_devices]).reshape(sizes)
    return Mesh(devs, tuple(axes.keys()))
