// Sharding-choice enumeration + resharding cost model.
//
// This is the TPU-native re-expression of the reference's substitution
// generators (src/runtime/substitution.cc:1726-1860): where the reference
// rewrites the PCG to insert Repartition/Replicate/Combine/Reduction ops
// around Linear/Attention/Conv (create_partition_linear_combine,
// create_replicate_linear_combine, create_partition_attention_combine, ...),
// we enumerate the *sharding choices* those rewrites produce directly:
//
//   dp       = partition sample dim              (Repartition on batch)
//   dp_col   = column-parallel weights           (Partition(out-dim)+Combine)
//   dp_row   = row-parallel weights + psum       (Replicate(in)+Reduction)
//   dp_head  = attribute parallelism over heads  (Partition(head)+Combine)
//   rep      = fully replicated
//
// An edge whose producer spec != consumer required spec carries a reshard
// cost — the GSPMD collective that the reference's parallel ops performed
// as Legion region copies.
#pragma once

#include <algorithm>
#include <cmath>
#include <cstdint>
#include <map>
#include <set>
#include <string>
#include <vector>

#include "ffs_graph.hpp"
#include "ffs_machine.hpp"

namespace ffsearch {

// Axis ids in a Spec entry: -1 replicated; 0..3 name the mesh axes of the
// (data, model, seq, expert) hybrid mesh — the N-D generalization of the
// reference's MachineView enumeration (graph.h:221) where a view is a
// device grid the op is laid out on.
constexpr int8_t kRep = -1;
constexpr int8_t kData = 0;
constexpr int8_t kModel = 1;
constexpr int8_t kSeq = 2;
constexpr int8_t kExpert = 3;
// sample parallelism (reference config.h:134 enable_sample_parallel): the
// sample dim sharded over BOTH the data and model axes jointly — a 2-D
// partition of the batch, used when an op's weights are replicated and
// the model axis would otherwise sit idle for it
constexpr int8_t kDataModel = 4;

using Spec = std::vector<int8_t>;

struct MeshShape {
  int dp = 1;  // data axis
  int mp = 1;  // model (tensor/attribute) axis
  int sp = 1;  // seq (context/ring) axis
  int ep = 1;  // expert axis
  int pp = 1;  // pipe axis (pipeline stages; r4 — the reference only
               // stubs OP_PIPELINE, ffconst.h:153). pp > 1 requires a
               // repeated-block graph; per-node choices then apply to the
               // inner (dp) mesh and the pipeline wraps them (ffs_sim.hpp
               // simulate_pipeline, which prices the GPipe-vs-circular
               // schedule and the microbatch count as dimensions; "_wus"
               // twins stay in play — the pipeline executor reduce-
               // scatters the stacked body grads over the data axes).
  int axis_size(int8_t axis) const {
    switch (axis) {
      case kData: return dp;
      case kModel: return mp;
      case kSeq: return sp;
      case kExpert: return ep;
      case kDataModel: return dp * mp;
      default: return 1;
    }
  }
  int total() const { return dp * mp * sp * ep * pp; }
};

inline Spec rep_spec(size_t rank) { return Spec(rank, kRep); }

// Named mesh axis ("data"/"model"/"seq"/"expert", e.g. repartition(axis=...))
// -> axis id; unrecognized/absent names fall back to the dim-derived
// default (dim 0 = batch = data, else model). Single definition shared by
// mesh pinning (ffs_search.cpp) and choice pricing below.
inline int8_t axis_from_name(const std::string& name, int64_t dim) {
  if (name == "data") return kData;
  if (name == "model") return kModel;
  if (name == "seq") return kSeq;
  if (name == "expert") return kExpert;
  return dim == 0 ? kData : kModel;
}

// How many ICI slices the data axis spans. Mesh legality (enumerate_meshes)
// keeps model/seq/expert inside one slice — their latency-sensitive
// collectives ride ICI — so only the gradient ring (data axis) crosses DCN.
inline int slices_spanned(const MeshShape& mesh, const MachineModel& m) {
  if (m.num_slices <= 1) return 1;
  int inner = mesh.mp * mesh.sp * mesh.ep;
  int dp_in_slice = std::max(1, m.chips_per_slice() / inner);
  return std::max(1, mesh.dp / dp_in_slice);
}

inline int shards_of(const Spec& s, const MeshShape& mesh) {
  int k = 1;
  for (int8_t e : s)
    if (e >= 0) k *= mesh.axis_size(e);
  return k;
}

struct Choice {
  std::string name;
  std::vector<Spec> out;               // per output tensor
  std::vector<Spec> in;                // required spec per input tensor
  std::map<std::string, Spec> param;   // per parameter
  double work_div = 1.0;               // compute FLOPs divided by this
  double psum_bytes = 0.0;             // partial-sum bytes reduced over model axis
  int psum_k = 1;
  int8_t psum_axis = kModel;           // mesh axis the psum rides (torus pricing)
  int8_t gather_axis = kModel;         // mesh axis a Combine gathers over
  double gradsync_bytes = 0.0;         // per-iteration gradient allreduce bytes
  int gradsync_k = 1;                  // chips in the gradient ring (dp * sp)
  bool wus = false;                    // weight-update sharding: gradsync runs
                                       // as reduce-scatter + all-gather and the
                                       // optimizer state shards over the ring
  bool ovl = false;                    // comms-compute overlap: the gradient
                                       // sync issues as size-targeted bucketed
                                       // async collectives in reverse-backward
                                       // order; only the un-hidden tail is
                                       // priced (overlap_price below), plus a
                                       // per-bucket launch overhead
  double bwd_psum_bytes = 0.0;         // backward-only partial-sum all-reduce
                                       // (col-parallel dX; replicated scatter
                                       // grads) over psum_axis
  double wgather_bytes = 0.0;          // forward-only weight all-gather over
                                       // psum_axis (tiny-batch row-parallel
                                       // lowering moves the kernel, once)
  double ring_bytes = 0.0;             // K/V bytes a device sends over a full
                                       // ring-attention rotation (seq axis)
  int ring_k = 1;                      // seq-ring size (hop count = ring_k-1)
  double gather_bytes = 0.0;           // all-gather a parallel-op boundary
  int gather_k = 1;                    // (Combine) forces
  std::string kernel;                  // searched kernel implementation
                                       // ("" = the op's default lowering;
                                       // "flash" / "fused" /
                                       // "conv_bn_fused" for the "_k:"
                                       // choice twins — ISSUE 15)
  bool remat = false;                  // rematerialization: checkpoint the
                                       // op's boundary (inputs) and
                                       // recompute its interior in backward
                                       // — node_act_bytes drops to zero and
                                       // node_cost charges one extra
                                       // forward ("_r" twins — ISSUE 20)
};

// ---- kernel-implementation dimension ("_k:<impl>" twins) -------------------
//
// The search decides HOW TO SHARD every op but, until this dimension,
// not WHICH KERNEL runs it. Ops with registered kernel alternatives
// spawn "_k:<impl>" twins of every sharding choice (composing with the
// "_wus"/"_ovl" suffix lattice — canonical order base[_wus][_ovl][_k:i]),
// each priced per-impl: measured "<guid>:fwd:<impl>" rows override a
// learned "<TYPE>:<impl>" class which overrides the analytic
// HBM-traffic delta vs the default lowering. FlexFlow/Unity's joint
// algorithmic+parallelization optimization (substitution.cc:2229)
// expressed on the suffix lattice.

// Default kernel impl of (node, choice) — what executes when no "_k:"
// twin is chosen. Attention's ring impl is carried by the existing
// "_ring" seq-sharding suffix (ring is exactly the seq-sharded
// execution, so its legality gate IS the seq mesh), not a "_k:" twin.
inline const char* kernel_default_impl(const Node& n, const Choice& c) {
  if (n.type == "MULTIHEAD_ATTENTION")
    return c.name.find("_ring") != std::string::npos ? "ring" : "einsum";
  if (n.type == "CONV2D") return "conv";
  if (c.wus) return "triad";
  return "";
}

// Keys a query of attention node `n` meets: the sequence, or the sliding
// window where the op has one that hides something (attr `window`,
// ops/attention.py), or under a mask that is not an interval of keys the
// op's own count (attr `keys_seen`: the block-diffusion mask's visible
// pairs over its queries). The scores are S x this, not S^2: what the einsum
// path keeps, what flash spares, and (through the op's own `flops`) the
// products both do. The kernel gate does not look at it: a window
// changes no shape, so it is admitted wherever `flash_shape_legal` is.
inline int64_t attention_keys_seen(const Node& n) {
  int64_t seq = n.output_shapes[0][1];
  int64_t seen = n.attrs.get("keys_seen").as_int(0);
  if (seen > 0) return std::min(seen, seq);
  int64_t window = n.attrs.get("window").as_int(0);
  return window > 0 ? std::min(window, seq) : seq;
}

// Structural legality of a kernel alternative on `n`: "" = legal, else a
// named rejection reason recorded in the search trace (the flash gate
// mirrors ops/pallas_kernels.flash_shape_legal — Q-block tile
// divisibility, sublane-aligned head dim and heads that tile the lanes
// of the kernels' [B, S, H*D] operands; conv_bn_fused mirrors the
// layout.py fold eligibility shipped as the `bn_fusable` node attr).
inline std::string kernel_gate(const Node& n, const std::string& impl,
                               bool training = true) {
  if (impl == "flash") {
    if (n.type != "MULTIHEAD_ATTENTION") return "not_attention";
    int64_t heads = n.attrs.get("num_heads").as_int(0);
    const Shape& os = n.output_shapes.empty() ? Shape{} : n.output_shapes[0];
    if (os.size() < 3 || heads <= 0) return "no_attention_geometry";
    int64_t seq = os[1];
    // a head's width is the op's own where it states one (attention.py
    // `head_dim`), else the split of the model width
    int64_t head_dim = n.attrs.get("head_dim").as_int(os.back() / heads);
    for (const Shape& is : n.input_shapes)
      if ((int64_t)is.size() < 2 || is[1] != seq)
        return "not_self_attention";
    if (seq % 128) return "seq_not_divisible_by_flash_tile_128";
    if (head_dim % 8) return "head_dim_not_lane_aligned_8";
    // upper bounds of the kernels' VMEM budget — MAX_FLASH_SEQ /
    // MAX_FLASH_HEAD_DIM in flexflow_tpu/ops/pallas_kernels.py; past
    // them the executor runs einsum, so pricing flash would misrank
    if (seq > 16384) return "seq_exceeds_flash_vmem_budget_16384";
    // past one 128-lane block a head is exactly two (the wide-head
    // kernels, a tile a grid step)
    if (head_dim > 128 && head_dim != 256)
      return "head_dim_exceeds_flash_vmem_budget_128";
    // latent attention's two-part score (attr `rope_head_dim`: a head's
    // query and key are `head_dim` lanes and that many more, rotated;
    // flash_shape_legal's `rope_dim`): a head is one block of 128 lanes,
    // and the heads' rotated parts tile 128-lane blocks among themselves
    int64_t rope_dim = n.attrs.get("rope_head_dim").as_int(0);
    if (rope_dim > 0 &&
        (head_dim != 128 || rope_dim % 8 || 128 % rope_dim ||
         heads % (128 / rope_dim)))
      return "latent_heads_do_not_tile_128_lanes";
    // the kernels take [B, S, H*D] operands in column blocks of the heads
    // that fill 128 lanes (pallas_kernels._heads_per_block): those have
    // to divide the heads and fill the lanes exactly, unless one block
    // is the whole row
    int64_t per_block = std::min<int64_t>(
        heads, std::max<int64_t>(1, 128 / std::max<int64_t>(head_dim, 1)));
    if (heads % per_block ||
        ((per_block * head_dim) % 128 && per_block != heads))
      return "heads_do_not_tile_128_lanes";
    // attention-prob dropout has no flash lowering (the kernel never
    // materializes the probabilities to drop) — training forwards take
    // the einsum path, so pricing flash would be a priced-vs-executed
    // gap; at inference dropout is off and flash stays legal
    if (training && n.attrs.get("dropout").as_double(0.0) > 0.0)
      return "attention_prob_dropout_unsupported";
    return "";
  }
  if (impl == "fused") {
    // fused optimizer-update region: collapses the WUS
    // RS -> update-triad -> AG chain into one dispatch
    if (n.param_bytes() <= 0) return "no_parameters";
    return "";
  }
  if (impl == "conv_bn_fused") {
    if (n.type != "CONV2D") return "not_conv";
    if (n.attrs.get("bn_fusable").as_int(0) == 0)
      return "no_foldable_batchnorm_consumer";
    return "";
  }
  return "unknown_impl";
}

// Layout-only ops XLA fuses into their producer/consumer on TPU: a slice,
// concat or reshape of a matmul output compiles to index arithmetic inside
// the neighboring fused kernel, not a standalone HBM round-trip. Charging
// them real traffic would make kernel-fusion rewrites (one wide matmul +
// split vs two narrow matmuls) look like losses when on hardware they win.
inline bool is_view_op(const std::string& t) {
  return t == "SPLIT" || t == "CONCAT" || t == "RESHAPE" || t == "FLAT" ||
         t == "IDENTITY" || t == "NOOP" || t == "INPUT";
}

// ---- rematerialization dimension ("_r" twins) ------------------------------
//
// A "_r" twin checkpoints the op's boundary (input) activations and
// recomputes its interior in backward: node_act_bytes drops to zero (the
// inputs are already counted at their producers; the output is rebuilt
// from them before the backward pass) and node_cost charges one extra
// forward in backward — through the same measured > learned > analytic
// chain, so a flash "_k:" parent's recompute prices the flash forward.
// The frontier DP's existing per-candidate memory terms then weigh freed
// HBM against recompute seconds per op: a memory-capped search picks "_r"
// exactly where the freed bytes buy a better mesh/batch (ISSUE 20).

// Structural legality of a remat twin of choice `c` on `n`: "" = legal,
// else a named rejection reason recorded in the search trace. The
// interior-vs-boundary test is impl-aware — einsum attention's interior
// includes the materialized [B,H,S,S] score tensor (the same score-bytes
// formula node_cost's flash delta subtracts); flash/ring never
// materialize it, so their interior is the output alone.
inline std::string remat_gate(const Node& n, const Choice& c,
                              bool training = true) {
  if (!training) return "not_training";
  if (is_view_op(n.type)) return "view_op_no_interior";
  // stateful interiors: recomputing the forward would re-advance state
  // (BN running stats) or re-sample masks/assignments (dropout, MoE
  // routing) — the recomputed interior would not match the one the
  // forward pass produced, so numerics drift
  if (n.type == "BATCH_NORM") return "stateful_interior";
  if (n.type == "DROPOUT" || n.attrs.get("dropout").as_double(0.0) > 0.0)
    return "dropout_interior";
  if (n.type == "EXPERTS" || n.type == "AGGREGATE" || n.type == "GROUP_BY" ||
      n.type == "TOPK" || n.type == "CACHE")
    return "stateful_interior";
  // the dropless layer's routing counts leave the step beside its output
  // (executor counters): a checkpointed interior would strand them
  if (n.type == "MOE_LAYER" || n.attrs.get("side_counters").as_double(0.0) > 0)
    return "counter_side_channel";
  // an output that other layers read lives to its last reader's
  // backward: a twin would price it as freed
  if (n.attrs.get("exports").as_double(0.0) > 0) return "exported_output";
  // the recompute re-runs the forward's collectives too; the pricing
  // charges compute only, so choices whose forward moves bytes (psum /
  // ring / gather / weight-gather) do not spawn twins — this also keeps
  // the emitted collective census identical (recompute duplicates
  // edges, not collectives)
  if (c.psum_bytes > 0 || c.ring_bytes > 0 || c.gather_bytes > 0 ||
      c.wgather_bytes > 0)
    return "forward_collective_interior";
  // interior (what the checkpoint frees) must exceed the boundary (what
  // it keeps): output bytes + impl-aware extras vs the UNIQUE input
  // tensors (self-attention's q=k=v count once)
  double interior = n.attrs.get("interior_bytes").as_double(0.0);
  for (size_t i = 0; i < n.output_shapes.size(); ++i)
    interior += (double)n.output_bytes((int)i);
  if (n.type == "MULTIHEAD_ATTENTION" && c.kernel != "flash" &&
      c.name.find("_ring") == std::string::npos &&
      !n.output_shapes.empty() && n.output_shapes[0].size() >= 2) {
    int64_t heads = n.attrs.get("num_heads").as_int(1);
    const Shape& os = n.output_shapes[0];
    interior += (double)os[0] * (double)heads * (double)os[1] *
                (double)attention_keys_seen(n) * 4.0;
  }
  double boundary = 0;
  std::vector<std::pair<int64_t, int>> seen;
  for (size_t i = 0; i < n.input_shapes.size(); ++i) {
    if (i < n.inputs.size() && n.inputs[i].src_guid >= 0) {
      std::pair<int64_t, int> key{n.inputs[i].src_guid, n.inputs[i].src_idx};
      if (std::find(seen.begin(), seen.end(), key) != seen.end()) continue;
      seen.push_back(key);
    }
    boundary += (double)n.input_bytes((int)i);
  }
  if (interior <= boundary) return "interior_not_larger_than_boundary";
  return "";
}

// ---- latency-hiding (comms-compute overlap) pricing -----------------------

// Bucket sizes the "_ovl" latency-hiding term sweeps (MB of wire payload
// per bucket). Small buckets start hiding earlier (the un-hideable tail is
// one bucket's comm) but each bucket pays a launch; the sweep's argmin is
// the searched bucket size "--overlap-bucket-mb auto" follows.
constexpr double kOvlBucketMB[] = {1.0, 2.0, 4.0, 8.0, 16.0, 32.0};
constexpr int kOvlBucketCount = 6;

struct OverlapPricing {
  double exposed = 0;    // comm time the step still waits on
  double hidden = 0;     // comm time priced as hidden under compute
  int buckets = 1;
  double bucket_mb = 0;  // argmin of the sweep
};

// Exposed time of `comm_s` seconds of gradient-sync comm issued as B
// size-targeted buckets in reverse-backward order, with `hideable_s` of
// compute still running when the first bucket's collective fires:
//   exposed(B) = max(comm/B, comm - hideable) + B * launch
// The comm/B floor is the last bucket's collective — produced by the last
// backward op, nothing left to hide it under (the optimizer-fusion
// prefetch window is part of hideable_s when the caller knows it).
// `wire_bytes` are post-comm_bytes_factor payload bytes (bucket count is
// a property of what moves on the wire).
inline OverlapPricing overlap_price(const MachineModel& m, double comm_s,
                                    double wire_bytes, double hideable_s) {
  OverlapPricing best;
  best.exposed = comm_s;
  if (comm_s <= 0) return best;
  bool first = true;
  for (int i = 0; i < kOvlBucketCount; ++i) {
    double mb = kOvlBucketMB[i];
    int B = std::max(1, (int)std::ceil(wire_bytes / (mb * 1e6)));
    double exp = std::max(comm_s / B, comm_s - std::max(0.0, hideable_s)) +
                 B * m.collective_launch_overhead;
    if (first || exp < best.exposed) {
      best.exposed = exp;
      best.hidden = std::max(0.0, comm_s - std::max(comm_s / B,
                                                    comm_s - hideable_s));
      best.buckets = B;
      best.bucket_mb = mb;
      first = false;
    }
  }
  return best;
}

// ---- reshard cost ---------------------------------------------------------

// Cost of transforming a tensor of `global_bytes` laid out as `a` into
// layout `b`. Approximations follow §2.3's op→collective mapping.
inline double reshard_cost(const Spec& a, const Spec& b, double global_bytes,
                           const MeshShape& mesh, const MachineModel& m) {
  if (a == b) return 0.0;
  int ka = shards_of(a, mesh), kb = shards_of(b, mesh);
  if (ka <= 1 && kb <= 1) return 0.0;
  // (dim, base axis) pairs; the joint kDataModel entry expands into its
  // base axes so data ⊂ data+model reads as pure additional slicing
  std::set<std::pair<int, int8_t>> sa, sb;
  auto expand = [](std::set<std::pair<int, int8_t>>& s, int i, int8_t ax) {
    if (ax == kDataModel) {
      s.insert({i, kData});
      s.insert({i, kModel});
    } else {
      s.insert({i, ax});
    }
  };
  for (size_t i = 0; i < a.size(); ++i) if (a[i] >= 0) expand(sa, (int)i, a[i]);
  for (size_t i = 0; i < b.size(); ++i) if (b[i] >= 0) expand(sb, (int)i, b[i]);
  bool a_in_b = std::includes(sb.begin(), sb.end(), sa.begin(), sa.end());
  if (a_in_b) return 0.0;  // pure additional slicing: local
  global_bytes *= m.comm_bytes_factor;  // bf16 activations on TPU
  bool b_in_a = std::includes(sa.begin(), sa.end(), sb.begin(), sb.end());
  int k_keep = 1;
  for (const auto& p : sa)
    if (sb.count(p)) k_keep *= mesh.axis_size(p.second);
  int kg = std::max(1, ka / k_keep);  // group size that must communicate
  if (b_in_a) {
    // all-gather: each chip ends with B/kb bytes, (1 - kb/ka) arriving remotely
    double out_bytes = global_bytes / kb;
    double frac = 1.0 - static_cast<double>(kb) / ka;
    return m.ici_latency * (kg - 1) + out_bytes * frac / m.ring_bw();
  }
  // mixed: all-to-all within the communicating group
  double per_chip = std::max(global_bytes / ka, global_bytes / kb);
  return m.ici_latency + per_chip * (kg - 1) / kg / m.ring_bw();
}

// ---- choice enumeration ---------------------------------------------------

namespace detail {

inline bool div_ok(int64_t size, int k) { return k > 0 && size % k == 0; }

// Spec for "shard sample dim 0 on data" given shape; kRep everywhere else.
inline Spec dp_spec(const Shape& shp, int dp) {
  Spec s = rep_spec(shp.size());
  if (!shp.empty() && dp > 1 && div_ok(shp[0], dp)) s[0] = kData;
  return s;
}

inline double pbytes(const Node& n) { return (double)n.param_bytes(); }

// Index of the Seq-role dim in output 0 (-1 if none).
inline int seq_dim_of(const Node& n) {
  if (n.roles.empty()) return -1;
  for (size_t d = 0; d < n.roles[0].size(); ++d)
    if (n.roles[0][d] == Role::Seq) return static_cast<int>(d);
  return -1;
}

// Total per-device parameter bytes under a choice's param shardings.
inline double sharded_param_bytes(const Node& n, const Choice& c,
                                  const MeshShape& mesh) {
  double b = 0;
  for (const auto& kv : n.params) {
    auto it = c.param.find(kv.first);
    int k = it != c.param.end() ? shards_of(it->second, mesh) : 1;
    b += (double)shape_elems(kv.second) * n.dtype_size / k;
  }
  return b;
}

// Tiny-batch weight movement — ONE rule for every row-parallel
// contraction (Linear, Conv2D, anything whose kernel shards the
// contraction dim): with at most one MXU tile edge (128) of output rows
// per data shard and an output smaller than its weight, GSPMD resolves
// the contraction by moving the WEIGHT — all-gather of the model-sharded
// kernel forward (once), all-reduce of the weight gradient backward
// (searched XDL emitted 7x the priced bytes before this term existed,
// fflint FFL202 / ROADMAP). At real batch sizes the term self-gates off.
// Mirrored exactly by analysis/dataflow.weight_movement_edges — the
// static edge rule and this priced term must agree or the census-parity
// test (tests/test_dataflow.py) fails.
inline void tiny_batch_weight_movement(Choice& c, const Node& n,
                                       double rows, int eff_dp) {
  if (rows > 0 && eff_dp > 0 && rows / eff_dp <= 128.0 &&
      (double)n.output_bytes(0) < pbytes(n)) {
    c.wgather_bytes += pbytes(n);
    c.bwd_psum_bytes += pbytes(n);
  }
}

}  // namespace detail

// Enumerate the legal sharding choices of `n` on mesh (dp, mp).
// `enable_pp` gates parameter/attribute parallelism
// (--enable-parameter-parallel, reference model.cc:3612); `enable_sp2`
// gates the 2-D sample partition (--enable-sample-parallel, config.h:134).
inline std::vector<Choice> enumerate_choices(const Node& n, const MeshShape& mesh,
                                             bool enable_pp,
                                             bool enable_sp2 = true,
                                             bool enable_wus = false,
                                             bool enable_ovl = false,
                                             bool enable_kernels = false,
                                             bool training = true,
                                             bool enable_remat = false) {
  using detail::div_ok;
  using detail::dp_spec;
  const int dp = mesh.dp, mp = mesh.mp;
  std::vector<Choice> out;
  const Shape& oshp = n.output_shapes.empty() ? Shape{} : n.output_shapes[0];
  const size_t orank = oshp.size();
  int64_t batch = orank ? oshp[0] : 0;
  bool sample0 = !n.roles.empty() && !n.roles[0].empty() &&
                 n.roles[0][0] == Role::Sample;

  auto base_choice = [&](const std::string& name) {
    Choice c;
    c.name = name;
    for (const auto& s : n.output_shapes) c.out.push_back(rep_spec(s.size()));
    for (const auto& s : n.input_shapes) c.in.push_back(rep_spec(s.size()));
    for (const auto& kv : n.params) c.param[kv.first] = rep_spec(kv.second.size());
    return c;
  };

  // choice 0: fully replicated — always legal
  out.push_back(base_choice("rep"));

  bool dp_legal = sample0 && dp > 1 && div_ok(batch, dp);
  auto make_dp = [&]() {
    Choice c = base_choice("dp");
    for (size_t i = 0; i < n.output_shapes.size(); ++i)
      c.out[i] = dp_spec(n.output_shapes[i], dp);
    for (size_t i = 0; i < n.input_shapes.size(); ++i) {
      // shard inputs that carry the same batch extent on dim 0
      const Shape& is = n.input_shapes[i];
      if (!is.empty() && is[0] == batch) c.in[i] = dp_spec(is, dp);
    }
    c.work_div = dp;
    c.gradsync_bytes = detail::pbytes(n);
    c.gradsync_k = dp;
    return c;
  };
  if (dp_legal) out.push_back(make_dp());

  // 2-D sample partition: batch over data x model jointly. Worth it for
  // ops whose params are replicated (their gradient ring widens to
  // dp*mp, but the work divides by dp*mp instead of dp while the model
  // axis would otherwise idle through this op).
  if (enable_sp2 && sample0 && mesh.mp > 1 && dp > 0 &&
      detail::div_ok(batch, (int64_t)dp * mesh.mp)) {
    Choice c = base_choice("sample2");
    for (size_t i = 0; i < n.output_shapes.size(); ++i) {
      const Shape& os = n.output_shapes[i];
      if (!os.empty() && os[0] == batch) c.out[i][0] = kDataModel;
    }
    for (size_t i = 0; i < n.input_shapes.size(); ++i) {
      const Shape& is = n.input_shapes[i];
      if (!is.empty() && is[0] == batch) c.in[i][0] = kDataModel;
    }
    c.work_div = (double)dp * mesh.mp;
    c.gradsync_bytes = detail::pbytes(n);
    c.gradsync_k = dp * mesh.mp;
    out.push_back(std::move(c));
  }

  const bool pp = enable_pp && mp > 1;
  const std::string& t = n.type;

  if (t == "LINEAR" && pp) {
    auto kit = n.params.find("kernel");
    if (kit != n.params.end() && kit->second.size() == 2) {
      int64_t in_dim = kit->second[0], out_dim = kit->second[1];
      int eff_dp = dp_legal ? dp : 1;
      double in_bytes = n.input_shapes.empty()
          ? 0.0 : (double)shape_elems(n.input_shapes[0]) * n.dtype_size;
      if (div_ok(out_dim, mp)) {  // column parallel: Partition(out)+Combine
        Choice c = dp_legal ? make_dp() : base_choice("col");
        c.name = dp_legal ? "dp_col" : "col";
        c.param["kernel"] = {kRep, kModel};
        if (c.param.count("bias")) c.param["bias"] = {kModel};
        c.out[0].back() = kModel;
        c.work_div = static_cast<double>(eff_dp) * mp;
        c.gradsync_bytes = detail::pbytes(n) / mp;
        c.gradsync_k = eff_dp;
        // backward dX contracts over the model-sharded out dim: per-chip
        // partials all-reduce (the Megatron pairing — col pays in bwd
        // what row pays in fwd). Was unpriced; fflint FFL202 caught
        // searched strategies emitting ARs the DP never costed (PR 3).
        c.bwd_psum_bytes = in_bytes / eff_dp;
        c.psum_k = mp;
        out.push_back(std::move(c));
      }
      if (div_ok(in_dim, mp)) {  // row parallel: Replicate+Reduction (psum)
        Choice c = dp_legal ? make_dp() : base_choice("row");
        c.name = dp_legal ? "dp_row" : "row";
        c.param["kernel"] = {kModel, kRep};
        c.in[0].back() = kModel;
        // output stays unsharded on model: psum of partials
        c.psum_bytes = (double)n.output_bytes(0) / eff_dp;
        c.psum_k = mp;
        c.work_div = static_cast<double>(eff_dp) * mp;
        c.gradsync_bytes = detail::pbytes(n) / mp;
        c.gradsync_k = eff_dp;
        // Rows = all output dims but the last (a [B,S,E] Linear runs
        // B*S MXU rows, not B).
        double rows = oshp.empty()
            ? 0.0 : (double)shape_elems(oshp) / oshp.back();
        detail::tiny_batch_weight_movement(c, n, rows, eff_dp);
        out.push_back(std::move(c));
      }
    }
  } else if (t == "EMBEDDING" && pp) {
    auto kit = n.params.find("kernel");
    if (kit != n.params.end() && kit->second.size() == 2) {
      int64_t vocab = kit->second[0], edim = kit->second[1];
      int eff_dp = dp_legal ? dp : 1;
      if (div_ok(edim, mp)) {
        Choice c = dp_legal ? make_dp() : base_choice("col");
        c.name = dp_legal ? "dp_col" : "col";
        c.param["kernel"] = {kRep, kModel};
        c.out[0].back() = kModel;
        c.work_div = static_cast<double>(eff_dp) * mp;
        c.gradsync_bytes = detail::pbytes(n) / mp;
        c.gradsync_k = eff_dp;
        out.push_back(std::move(c));
      }
      if (div_ok(vocab, mp)) {  // vocab-sharded: masked lookup + psum
        Choice c = dp_legal ? make_dp() : base_choice("row");
        c.name = dp_legal ? "dp_row" : "row";
        c.param["kernel"] = {kModel, kRep};
        c.psum_bytes = (double)n.output_bytes(0) / eff_dp;
        c.psum_k = mp;
        c.work_div = static_cast<double>(eff_dp) * mp;
        // XLA cannot keep the dkernel scatter vocab-sharded (the update
        // rows are index-dependent): the gradient materializes replicated
        // and all-reduces the FULL table over the model axis, and the
        // data ring then carries full table bytes too — the ~7x
        // underpricing fflint FFL202 flagged on searched XDL (ROADMAP).
        c.bwd_psum_bytes = detail::pbytes(n);
        c.gradsync_bytes = detail::pbytes(n);
        c.gradsync_k = eff_dp;
        out.push_back(std::move(c));
      }
    }
  } else if (t == "CONV2D" && pp && n.attrs.get("groups").as_int(1) == 1) {
    auto kit = n.params.find("kernel");  // OIHW
    if (kit != n.params.end() && kit->second.size() == 4) {
      int64_t oc = kit->second[0], ic = kit->second[1];
      int eff_dp = dp_legal ? dp : 1;
      double in_bytes = n.input_shapes.empty()
          ? 0.0 : (double)shape_elems(n.input_shapes[0]) * n.dtype_size;
      if (div_ok(oc, mp)) {
        Choice c = dp_legal ? make_dp() : base_choice("col");
        c.name = dp_legal ? "dp_col" : "col";
        c.param["kernel"] = {kModel, kRep, kRep, kRep};
        if (c.param.count("bias")) c.param["bias"] = {kModel};
        if (c.out[0].size() == 4) c.out[0][1] = kModel;  // NCHW channel
        c.work_div = static_cast<double>(eff_dp) * mp;
        c.gradsync_bytes = detail::pbytes(n) / mp;
        c.gradsync_k = eff_dp;
        // backward dX contracts over the channel-sharded out dim —
        // same unpriced AR as the col-parallel Linear (FFL202, PR 3)
        c.bwd_psum_bytes = in_bytes / eff_dp;
        c.psum_k = mp;
        out.push_back(std::move(c));
      }
      if (div_ok(ic, mp)) {
        Choice c = dp_legal ? make_dp() : base_choice("row");
        c.name = dp_legal ? "dp_row" : "row";
        c.param["kernel"] = {kRep, kModel, kRep, kRep};
        if (c.in[0].size() == 4) c.in[0][1] = kModel;
        c.psum_bytes = (double)n.output_bytes(0) / eff_dp;
        c.psum_k = mp;
        c.work_div = static_cast<double>(eff_dp) * mp;
        c.gradsync_bytes = detail::pbytes(n) / mp;
        c.gradsync_k = eff_dp;
        // Conv MXU rows = N*H*W of the output (channel is the
        // contraction's free dim).
        double rows = n.output_shapes[0].size() == 4
            ? (double)(n.output_shapes[0][0] * n.output_shapes[0][2] *
                       n.output_shapes[0][3])
            : (double)batch;
        detail::tiny_batch_weight_movement(c, n, rows, eff_dp);
        out.push_back(std::move(c));
      }
    }
  } else if (t == "MULTIHEAD_ATTENTION" && pp) {
    int64_t heads = n.attrs.get("num_heads").as_int(0);
    int64_t kv_heads = n.attrs.get("num_kv_heads").as_int(heads);
    if (kv_heads <= 0) kv_heads = heads;
    if (heads > 0 && div_ok(heads, mp)) {
      // attribute parallelism: shard the head axis of every weight whose
      // dim 0 == num_heads (wq [H,E,D], wo [H,D,E]) — the reference's
      // create_partition_attention_combine (substitution.cc:1764). Under
      // GQA (attention.cc:214 head-count split) wk/wv carry num_kv_heads
      // on dim 0: shard them too when kv_heads divides mp; otherwise they
      // stay replicated and their gradient ring spans ALL dp*mp chips —
      // priced separately so the search sees the true GQA cost.
      int eff_dp = dp_legal ? dp : 1;
      Choice c = dp_legal ? make_dp() : base_choice("head");
      c.name = dp_legal ? "dp_head" : "head";
      bool any = false;
      bool kv_sharded = div_ok(kv_heads, mp);
      double sharded_bytes = 0.0, replicated_bytes = 0.0;
      for (const auto& kv : n.params) {
        int64_t dim0 = kv.second.empty() ? 0 : kv.second[0];
        double bytes = (double)shape_elems(kv.second) * n.dtype_size;
        if (dim0 == heads || (dim0 == kv_heads && kv_sharded)) {
          Spec s = rep_spec(kv.second.size());
          s[0] = kModel;
          c.param[kv.first] = s;
          sharded_bytes += bytes;
          any = true;
        } else {
          replicated_bytes += bytes;
        }
      }
      if (any) {
        c.psum_bytes = (double)n.output_bytes(0) / eff_dp;  // output proj psum
        c.psum_k = mp;
        c.work_div = static_cast<double>(eff_dp) * mp;
        // head-sharded params ring over dp; replicated (kv) params ring
        // over every chip — fold both into one equivalent-bytes ring
        // (a ring of k chips moves ~2B/bw per chip regardless of k, so
        // payload, not ring size, dominates)
        if (eff_dp > 1) {
          c.gradsync_bytes = sharded_bytes / mp + replicated_bytes;
          c.gradsync_k = eff_dp;
        } else if (replicated_bytes > 0) {
          // pure TP: replicated kv grads still allreduce over mp
          c.gradsync_bytes = replicated_bytes;
          c.gradsync_k = mp;
        }
        out.push_back(std::move(c));
      }
    }
  } else if (t == "REPARTITION" || t == "COMBINE" || t == "REPLICATE" ||
             t == "REDUCTION") {
    // Explicit PCG constraint boundaries (ops/parallel_ops.py): price the
    // collective each boundary forces, so the substitution engine's
    // moves/eliminations of these nodes change the searched cost. The
    // degree must equal the mesh axis extent to be realizable (the Python
    // strategy applier enforces the same for Repartition). A Repartition
    // may NAME its mesh axis (repartition(axis=...), serialized as
    // mesh_axis) — cost the axis the executor will actually use.
    int64_t dim = n.attrs.get("dim").as_int(0);
    int64_t deg = n.attrs.get("degree").as_int(1);
    int8_t ax = axis_from_name(n.attrs.get("mesh_axis").as_string(), dim);
    if (deg > 1 && mesh.axis_size(ax) == deg && orank > 0 &&
        dim < (int64_t)orank) {
      out.clear();
      Choice c = base_choice("constrain");
      if (t == "REPARTITION") {
        c.out[0][dim] = ax;        // output constrained sharded on dim
        c.in[0] = c.out[0];        // producer pays the reshard at the edge
      } else if (t == "COMBINE") {
        c.in[0][dim] = ax;         // consumes the sharded layout...
        c.gather_bytes = (double)n.output_bytes(0);  // ...and gathers it
        c.gather_k = (int)deg;
        c.gather_axis = ax;
      } else if (t == "REDUCTION") {
        c.psum_bytes = (double)n.output_bytes(0);
        c.psum_k = (int)deg;
        c.psum_axis = ax;
      }
      // REPLICATE: in/out replicated — the reshard from a sharded producer
      // is the broadcast cost, charged on the input edge
      out.push_back(std::move(c));
    }
  } else if (t == "FUSED_PARALLEL") {
    // fuse_parallel_ops result (substitution.cc:1925 analog): the whole
    // chain is ONE boundary — compose the steps into the final layout and
    // charge a single reshard at the producer edge (vs the two separate
    // collectives the unfused pair priced — the reason fusing wins)
    const Json& steps = n.attrs.get("ops");
    if (!steps.is_null() && orank > 0) {
      Spec sp_ = rep_spec(orank);
      bool legal = true;
      for (const Json& st_ : steps.items()) {
        std::string kind = st_[0].as_string();
        int64_t dim = st_[1].as_int(0);
        int64_t deg = st_[2].as_int(1);
        // optional 4th element: the step's mesh-axis name
        int8_t ax = axis_from_name(
            st_.items().size() > 3 ? st_[3].as_string() : std::string(),
            dim);
        if (kind == "REPARTITION") {
          if (dim < 0 || dim >= (int64_t)orank ||
              mesh.axis_size(ax) != deg || oshp[dim] % deg) {
            legal = false;
            break;
          }
          sp_[dim] = ax;
        } else if (kind == "COMBINE") {
          if (dim < 0 || dim >= (int64_t)orank ||
              mesh.axis_size(ax) != deg) {
            legal = false;
            break;
          }
          sp_[dim] = kRep;
        } else if (kind == "REPLICATE") {
          sp_ = rep_spec(orank);
        } else {
          legal = false;
          break;
        }
      }
      if (legal) {
        out.clear();
        Choice c = base_choice("fused_constrain");
        c.out[0] = sp_;
        c.in[0] = sp_;  // one reshard, charged at the producer edge
        out.push_back(std::move(c));
      }
    }
  } else if (t == "EXPERTS" && mesh.ep > 1) {
    // expert parallelism: the stacked expert weights [E, ...] shard over
    // the 'expert' mesh axis; token dispatch/combine is the
    // reduce-scatter + all-gather exchange of parallel/expert.py (cost ~ an
    // all-reduce of the [E, C, D] grouped activations). This is the SPMD
    // form of the reference's per-expert device placement (moe.cc:65-83).
    int64_t experts = n.attrs.get("n_experts").as_int(0);
    int ep = mesh.ep;
    if (experts > 0 && div_ok(experts, ep)) {
      const size_t base_count = out.size();
      for (size_t bi = 0; bi < base_count; ++bi) {
        Choice c = out[bi];
        int eff_dp = (!c.out[0].empty() && c.out[0][0] == kData) ? dp : 1;
        // the runtime shards tokens over data x expert jointly
        // (parallel/expert.py falls back to the dense path otherwise) —
        // don't offer a plan the executor would refuse
        if (!div_ok(batch, (int64_t)eff_dp * ep)) continue;
        c.name += "_ep";
        for (auto& kv : c.param)
          if (!kv.second.empty() && kv.second[0] == kRep)
            kv.second[0] = kExpert;
        c.work_div *= ep;
        // grouped activations [E, C, D] (f32) cross the expert axis twice
        // (reduce-scatter in, all-gather out) ~= one all-reduce
        double alpha_cap = n.attrs.get("alpha").as_double(2.0);
        double kk = (double)n.attrs.get("k").as_int(1);
        int64_t b_tokens = orank ? oshp[0] : 1;
        int64_t d_model = orank ? oshp.back() : 1;
        c.psum_bytes = alpha_cap * kk * (double)b_tokens * d_model * 4.0 /
                       eff_dp;
        c.psum_k = ep;
        c.psum_axis = kExpert;
        c.gradsync_bytes = detail::pbytes(n) / ep;
        c.gradsync_k = eff_dp;
        out.push_back(std::move(c));
      }
    }
  } else if ((t.rfind("EW_", 0) == 0 || t == "RELU" || t == "GELU" ||
              t == "SIGMOID" || t == "TANH" || t == "ELU" || t == "EXP" ||
              t == "SIN" || t == "COS" || t == "POW" || t == "RSQRT" ||
              t == "IDENTITY" || t == "DROPOUT" || t == "CAST" ||
              t.rfind("SCALAR_", 0) == 0) && pp && orank >= 2 &&
             div_ok(oshp.back(), mp)) {
    // follow-style ops can also carry a model-sharded last dim so a
    // col-parallel producer's layout flows through without a gather
    Choice c = dp_legal ? make_dp() : base_choice("mp_last");
    c.name = dp_legal ? "dp_mp_last" : "mp_last";
    c.out[0].back() = kModel;
    for (size_t i = 0; i < n.input_shapes.size(); ++i) {
      const Shape& is = n.input_shapes[i];
      if (!is.empty() && is.back() == oshp.back()) c.in[i].back() = kModel;
    }
    c.work_div = static_cast<double>(dp_legal ? dp : 1) * mp;
    out.push_back(std::move(c));
  }

  // ---- sequence/context parallelism over the 'seq' axis ------------------
  // New scope vs the reference (SURVEY §5.7): attention becomes ring
  // attention (K/V rotate on the ICI ring via ppermute,
  // flexflow_tpu/parallel/ring_attention.py); seq-batchlike ops simply
  // carry the seq-sharded layout, dividing their work like an extra batch
  // axis. Every base choice spawns a seq-extended variant so hybrid
  // dp x mp x sp strategies compose.
  const int sp = mesh.sp;
  int sd = detail::seq_dim_of(n);
  if (sp > 1 && sd >= 0 && sd < (int)orank && div_ok(oshp[sd], sp)) {
    const int64_t seq_extent = oshp[sd];
    // an op that marks a Seq role declares that dim position-independent
    // (shardable); attention additionally needs the ring rewrite and only
    // supports it for self-attention (equal q/k/v sequence extents)
    bool is_attn = t == "MULTIHEAD_ATTENTION";
    bool self_attn = true;
    for (const Shape& is : n.input_shapes)
      if ((int)is.size() <= sd || is[sd] != seq_extent) self_attn = false;
    if (!is_attn || self_attn) {
      const size_t base_count = out.size();
      for (size_t bi = 0; bi < base_count; ++bi) {
        Choice c = out[bi];
        if ((int)c.out[0].size() <= sd || c.out[0][sd] != kRep) continue;
        c.name += is_attn ? "_ring" : "_sp";
        for (size_t i = 0; i < n.output_shapes.size(); ++i) {
          const Shape& os = n.output_shapes[i];
          if ((int)os.size() > sd && os[sd] == seq_extent &&
              c.out[i][sd] == kRep)
            c.out[i][sd] = kSeq;
        }
        for (size_t i = 0; i < n.input_shapes.size(); ++i) {
          const Shape& is = n.input_shapes[i];
          if ((int)is.size() > sd && is[sd] == seq_extent &&
              c.in[i][sd] == kRep)
            c.in[i][sd] = kSeq;
        }
        c.work_div *= sp;
        // row-parallel partial sums shrink with the seq-sharded output
        if (c.psum_bytes > 0) c.psum_bytes /= sp;
        if (c.bwd_psum_bytes > 0) c.bwd_psum_bytes /= sp;
        if (is_attn) {
          // K/V rotation cost: each device sends its projected K+V block
          // (sp-1) times around the seq ring. Block bytes = global K+V
          // (~2x the [B,S,E] output) over all sharding of B/H/S.
          int eff_dp = (!c.out[0].empty() && c.out[0][0] == kData) ? dp : 1;
          auto wk = c.param.find("wk");
          int eff_mp = (wk != c.param.end() && !wk->second.empty() &&
                        wk->second[0] == kModel) ? mesh.mp : 1;
          double kv_global = 2.0 * (double)n.output_bytes(0);
          c.ring_bytes = kv_global / ((double)eff_dp * eff_mp * sp) * (sp - 1);
          c.ring_k = sp;
        }
        // weights are replicated over the seq axis: their gradients reduce
        // over seq as well as data
        if (!n.params.empty() && n.param_bytes() > 0) {
          if (c.gradsync_bytes > 0) {
            c.gradsync_k *= sp;
          } else {
            c.gradsync_bytes = detail::sharded_param_bytes(n, c, mesh);
            c.gradsync_k = sp;
          }
        }
        out.push_back(std::move(c));
      }
    }
  }

  // ---- weight-update sharding (WUS) variants ------------------------------
  // Every choice that carries a data-ring gradient sync spawns a "_wus"
  // twin: the sync prices as reduce-scatter + all-gather instead of an
  // all-reduce, and the optimizer state (+ f32 master) shards over the
  // ring — node_param_memory and the simulator's update-traffic term
  // divide by gradsync_k. The DP weighs both forms per mesh, so WUS is a
  // searched strategy dimension, not a global toggle (ISSUE 4).
  // Twins only exist on meshes with a data ring: the executor shards the
  // master/optimizer state over the DATA axes, so a pure-TP mesh (dp=1)
  // has no shard dimension for WUS to use.
  if (enable_wus && mesh.dp > 1) {
    const size_t base_count = out.size();
    for (size_t bi = 0; bi < base_count; ++bi) {
      const Choice& b = out[bi];
      if (b.gradsync_bytes <= 0 || b.gradsync_k <= 1) continue;
      Choice c = b;
      c.name += "_wus";
      c.wus = true;
      out.push_back(std::move(c));
    }
  }

  // ---- comms-compute overlap ("_ovl") variants ----------------------------
  // Every "_wus" choice spawns an "_ovl" twin: the gradient sync issues
  // as bucketed async collectives structured so XLA hides them under
  // remaining backward compute, and the DP prices only the un-hidden
  // tail plus per-bucket launch overhead (ISSUE 9). The twin can WIN at
  // higher byte counts than a low-byte sync choice — latency hiding is
  // a searched dimension, not an executor flag. Only WUS parents spawn
  // twins because the runtime's bucket chaining rides on the WUS
  // reduce-scatter shard constraints (executor._chain_constrained) —
  // pricing hiding the executor cannot deliver would misrank strategies.
  if (enable_ovl) {
    const size_t base_count = out.size();
    for (size_t bi = 0; bi < base_count; ++bi) {
      const Choice& b = out[bi];
      if (!b.wus) continue;
      if (b.gradsync_bytes <= 0 || b.gradsync_k <= 1) continue;
      Choice c = b;
      c.name += "_ovl";
      c.ovl = true;
      out.push_back(std::move(c));
    }
  }

  // ---- kernel-implementation ("_k:<impl>") variants ------------------------
  // Runs LAST so the kernel suffix composes with every sharding/"_wus"/
  // "_ovl" twin already enumerated (canonical base[_wus][_ovl][_k:impl]).
  // Each twin is a different LOWERING of the same sharded computation:
  // identical specs and collectives, different compute/update pricing
  // (node_cost's per-impl chain). Legality gates fire here; their named
  // reasons are re-derived into the search trace by per_op_trace.
  if (enable_kernels) {
    const size_t base_count = out.size();
    for (size_t bi = 0; bi < base_count; ++bi) {
      // by VALUE: the push_backs below may reallocate `out`, and a
      // reference into it would dangle across the checks that follow
      const Choice b = out[bi];
      // flash attention: streams K/V through VMEM per Q block — no
      // materialized [B,H,S,S] score tensor in HBM. Not on "_ring"
      // parents: ring attention IS its own kernel (impl "ring").
      if (t == "MULTIHEAD_ATTENTION" &&
          b.name.find("_ring") == std::string::npos &&
          kernel_gate(n, "flash", training).empty()) {
        Choice c = b;
        c.name += "_k:flash";
        c.kernel = "flash";
        out.push_back(std::move(c));
      }
      // train-time Conv+BN fused region (the eval fold's legality,
      // shipped as the bn_fusable attr, reused at train time)
      if (t == "CONV2D" && training &&
          kernel_gate(n, "conv_bn_fused").empty()) {
        Choice c = b;
        c.name += "_k:conv_bn_fused";
        c.kernel = "conv_bn_fused";
        out.push_back(std::move(c));
      }
      // fused optimizer update: the WUS RS -> triad -> AG chain
      // collapses from three dispatches to one fused region. Attention
      // keeps its "_k:" dimension for the attention core.
      if (training && b.wus && t != "MULTIHEAD_ATTENTION" &&
          kernel_gate(n, "fused").empty()) {
        Choice c = b;
        c.name += "_k:fused";
        c.kernel = "fused";
        out.push_back(std::move(c));
      }
    }
  }

  // ---- rematerialization ("_r") variants ----------------------------------
  // Runs after the kernel block so "_r" is the final suffix of the
  // canonical lattice base[_wus][_ovl][_k:impl][_r] and the recompute
  // prices the actual lowering (a flash parent's "_r" twin recomputes
  // the flash forward). Legality gates (remat_gate) fire here; their
  // named reasons are re-derived into the search trace by per_op_trace.
  if (enable_remat && training) {
    const size_t base_count = out.size();
    for (size_t bi = 0; bi < base_count; ++bi) {
      // by VALUE: the push_backs below may reallocate `out`
      const Choice b = out[bi];
      if (!remat_gate(n, b, training).empty()) continue;
      Choice c = b;
      c.name += "_r";
      c.remat = true;
      out.push_back(std::move(c));
    }
  }
  return out;
}

// ---- per-node cost given a choice ----------------------------------------

struct NodeCost {
  double fwd = 0, bwd = 0, comm = 0, gradsync = 0;
  // comm seconds the "_ovl" pricing treated as hidden under compute
  // (informational — never part of total(); the simtrace hidden lanes
  // and the search trace's overlap column read it)
  double gradsync_hidden = 0;
  // bucket size (MB) the "_ovl" sweep committed to, 0 for non-ovl
  // choices — the per-op searched value "--overlap-bucket-mb auto"
  // follows (byte-weighted across the winning assignment)
  double ovl_bucket_mb = 0;
  int ovl_buckets = 0;
  // which model priced fwd/bwd (SRC_ANALYTIC / SRC_LEARNED /
  // SRC_MEASURED) — recorded per candidate in the search trace and per
  // node in the simulate response so every priced number is traceable
  // to its source
  int8_t src = SRC_ANALYTIC;
  double total() const { return fwd + bwd + comm + gradsync; }
};

// The learned model's feature vector for (node, choice) — MUST mirror
// flexflow_tpu/costmodel/corpus.py featurize() (see ffs_machine.hpp).
inline void learned_features(const Node& n, const Choice& c,
                             double (&f)[kLearnedFeatures]) {
  double div = std::max(1.0, c.work_div);
  f[0] = std::log1p(n.fwd_flops / div);
  f[1] = std::log1p((double)n.total_io_bytes() / div);
  f[2] = std::log1p((double)n.param_bytes());
  f[3] = std::log(div);
}

// Learned per-chip (fwd, bwd) compute seconds for (node, choice):
// false when no table is loaded, the op class is below the coverage
// gate (absent from the table), or the query falls outside the trained
// feature hull — callers then keep the analytic roofline. Shared by
// node_cost and the search trace's learned-vs-analytic columns.
inline bool learned_compute(const Node& n, const Choice& c,
                            const MachineModel& m, double* fwd,
                            double* bwd, bool* matched_impl = nullptr) {
  if (matched_impl != nullptr) *matched_impl = false;
  if (m.learned.empty()) return false;
  double f[kLearnedFeatures];
  learned_features(n, c, f);
  // compute-kernel twins prefer their per-impl class ("TYPE:impl",
  // trained on per-impl corpus rows); base class is the fallback —
  // `matched_impl` reports which matched, so node_cost knows whether
  // the analytic per-impl delta still applies on top
  if (!c.kernel.empty() && c.kernel != "fused" &&
      m.learned_predict(n.type + ":" + c.kernel, f, fwd, bwd)) {
    if (matched_impl != nullptr) *matched_impl = true;
    return true;
  }
  return m.learned_predict(n.type, f, fwd, bwd);
}

// Optimizer update-triad HBM time of (node, choice): read p + read g +
// write p (3x the shard's param bytes) + read+write per optimizer-state
// copy; WUS divides by the gradient ring. The "_k:fused" kernel twin
// collapses the RS-epilogue / per-leaf update kernels / AG-prologue
// chain into ONE fused region: the separate update kernels' re-read of
// p between dispatches disappears (3 -> 2 param round trips) and two of
// the three dispatch launches are saved. Shared by node_cost's hide
// window and its final update term so both price the same triad.
inline double update_triad_time(const Node& n, const Choice& c,
                                const MeshShape& mesh, const MachineModel& m,
                                double opt_state_factor) {
  if (opt_state_factor < 0 || n.param_bytes() <= 0) return 0.0;
  double copies = (c.kernel == "fused") ? 2.0 : 3.0;
  double upd = detail::sharded_param_bytes(n, c, mesh) *
               (copies + 2.0 * opt_state_factor) / m.hbm_bw;
  if (c.wus && c.gradsync_k > 1) upd /= c.gradsync_k;
  if (c.kernel == "fused")
    upd = std::max(0.0, upd - 2.0 * m.collective_launch_overhead);
  return upd;
}

// Per-node forward/backward time. When a measured-cost table is supplied
// (real-chip microbenchmarks, the analog of the reference's
// measure_operator_cost cache, src/runtime/model.cu:38-74 +
// simulator.h:750-752), entries "<guid>:fwd" / "<guid>:bwd" override the
// analytic roofline; sharded work scales as measured/work_div. Backward is
// measured separately — not assumed 2x forward — when the profiler provides
// it.
// `opt_state_factor >= 0` additionally folds the optimizer update-triad
// time (read p/g, write p, + 2x per state copy, HBM-bound) into
// nc.gradsync — for the frontier DP only, which otherwise cannot see the
// per-chip update traffic a WUS choice divides by the gradient ring. The
// taskgraph simulator prices its own global update task and passes the
// default (-1) here.
inline NodeCost node_cost(const Node& n, const Choice& c, const MeshShape& mesh,
                          const MachineModel& m, bool training,
                          const MeasuredCosts* measured = nullptr,
                          double opt_state_factor = -1.0) {
  NodeCost nc;
  if (is_view_op(n.type)) return nc;  // fused away by XLA: free
  double div = std::max(1.0, c.work_div);
  // kernel twins that change the COMPUTE lowering (flash,
  // conv_bn_fused; "fused" only moves the update term): their measured
  // rows are keyed "<guid>:fwd:<impl>" and their learned class
  // "<TYPE>:<impl>" — the base rows/class time the DEFAULT lowering and
  // must not silently price a different kernel
  const bool compute_impl = !c.kernel.empty() && c.kernel != "fused";
  const double* mfwd = nullptr;
  const double* mbwd = nullptr;
  if (measured != nullptr) {
    const std::string kf = std::to_string(n.guid) + ":fwd" +
                           (compute_impl ? ":" + c.kernel : std::string());
    const std::string kb = std::to_string(n.guid) + ":bwd" +
                           (compute_impl ? ":" + c.kernel : std::string());
    auto itf = measured->find(kf);
    if (itf != measured->end()) mfwd = &itf->second;
    auto itb = measured->find(kb);
    if (itb != measured->end()) mbwd = &itb->second;
  }
  double flop = n.fwd_flops / div;
  double bytes = (double)n.total_io_bytes() / div;
  // shape-aware MXU efficiency for matmul-carrying ops: derive (M,N,K)
  // from the node's shapes, then shrink the dim the CHOICE shards —
  // a col-parallel Linear runs an N/mp-wide matmul per chip, a
  // dp-sharded one an M/dp-tall one. Measured costs override all this.
  double eff = -1.0;
  if (n.type == "LINEAR" || n.type == "CONV2D" || n.type == "SHORT_CONV") {
    // per-chip (M, N, K) from the choice's STRUCTURED per-dim axis
    // assignments (not its name, which would rot as choices grow): each
    // sharded dim divides by its mesh-axis extent
    auto dim_shards = [&](const std::vector<Spec>& specs, size_t ti,
                          size_t di) -> double {
      if (ti >= specs.size() || di >= specs[ti].size()) return 1.0;
      int8_t e = specs[ti][di];
      return e >= 0 ? (double)mesh.axis_size(e) : 1.0;
    };
    double M = 0, N = 0, K = 0;
    if (n.type == "LINEAR" && !n.input_shapes.empty() &&
        !n.input_shapes[0].empty() && !n.output_shapes.empty()) {
      const Shape& is = n.input_shapes[0];
      const Shape& os = n.output_shapes[0];
      K = (double)is.back() / dim_shards(c.in, 0, is.size() - 1);
      M = 1;
      for (size_t i = 0; i + 1 < os.size(); ++i)
        M *= (double)os[i] / dim_shards(c.out, 0, i);
      N = (double)os.back() / dim_shards(c.out, 0, os.size() - 1);
    } else if (n.type == "SHORT_CONV" && !n.output_shapes.empty() &&
               n.output_shapes[0].size() == 3) {
      // the gated short convolution's FLOPs are its two products,
      // [B S, E] x [E, 3 E] and [B S, E] x [E, E] (the depthwise taps
      // between them are bytes, counted in interior_bytes): priced at
      // the narrower one's efficiency, rows divided as the choice
      // shards the batch
      const Shape& os = n.output_shapes[0];
      M = (double)os[0] / dim_shards(c.out, 0, 0) * (double)os[1];
      N = K = (double)os[2];
    } else if (n.type == "CONV2D") {
      auto kit = n.params.find("kernel");  // OIHW
      if (kit != n.params.end() && kit->second.size() == 4 &&
          !n.output_shapes.empty() && n.output_shapes[0].size() == 4) {
        const Shape& os = n.output_shapes[0];
        N = (double)kit->second[0] / dim_shards(c.out, 0, 1);
        K = (double)(kit->second[1] * kit->second[2] * kit->second[3]) /
            dim_shards(c.in, 0, 1);
        M = (double)os[0] / dim_shards(c.out, 0, 0) *
            (double)(os[2] * os[3]);
      }
    }
    // conv-class asymptote: measured conv MFU sits far below matmul MFU
    // even channels-last (per-op-class calibration, ffs_machine.hpp)
    double asym = (n.type == "CONV2D") ? m.conv_efficiency
                                       : m.mxu_efficiency;
    if (M > 0 && N > 0 && K > 0)
      eff = m.matmul_efficiency(M, N, K, asym);
    else if (n.type == "CONV2D")
      eff = m.conv_efficiency;  // geometry unavailable: flat conv class
  }
  // pricing priority: measured per-op profile > learned regression >
  // analytic roofline. The learned model predicts per-chip SHARDED
  // seconds directly (its targets were measured/work_div and work_div
  // is a feature), so no further division applies. Kernel twins prefer
  // a per-impl learned class; absent one, the DEFAULT lowering's price
  // (base learned or analytic) gets the impl's analytic HBM-traffic
  // delta applied below.
  double lfwd = 0, lbwd = 0;
  bool learned_is_impl = false;
  bool has_learned =
      mfwd == nullptr &&
      learned_compute(n, c, m, &lfwd, &lbwd, &learned_is_impl);
  if (mfwd != nullptr) {
    nc.fwd = std::max(*mfwd / div, m.min_op_time);
    nc.src = SRC_MEASURED;
  } else if (has_learned) {
    nc.fwd = std::max(lfwd, m.min_op_time);
    nc.src = SRC_LEARNED;
  } else {
    nc.fwd = m.compute_time(flop, bytes, n.dtype_size, eff);
  }
  if (training) {
    if (mbwd != nullptr)
      nc.bwd = std::max(*mbwd / div, m.min_op_time);
    else if (has_learned)
      nc.bwd = std::max(lbwd, m.min_op_time);
    else
      nc.bwd = 2.0 * nc.fwd;  // dX + dW passes
  }
  if (compute_impl && mfwd == nullptr && !learned_is_impl) {
    // analytic per-impl delta on the default lowering's price, floored
    // at the pure flop bound (the impl removes HBM traffic, not math)
    double asym = eff > 0 ? eff : m.mxu_efficiency;
    double peak = m.flops * asym * (n.dtype_size <= 2 ? 1.0 : 0.5);
    double floor_f = flop / peak + m.min_op_time;
    double floor_b = 2.0 * flop / peak + m.min_op_time;
    if (c.kernel == "flash") {
      // HBM-traffic model vs the materialized-scores einsum: the
      // default lowering round-trips the f32 [B,H,S,S] probability
      // tensor (write+read fwd; recomputed probs + dP write+read bwd)
      // — flash keeps scores in VMEM. The calibrated einsum price
      // implicitly contains that traffic; subtract it.
      int64_t heads = n.attrs.get("num_heads").as_int(1);
      const Shape& os = n.output_shapes[0];
      double score_b = (double)os[0] * heads * (double)os[1] *
                       (double)attention_keys_seen(n) * 4.0 / div;
      nc.fwd = std::max(nc.fwd - 2.0 * score_b / m.hbm_bw, floor_f);
      if (training)
        nc.bwd = std::max(nc.bwd - 4.0 * score_b / m.hbm_bw, floor_b);
    } else if (c.kernel == "conv_bn_fused") {
      // fused Conv+BN region: the conv output's write + the BN's read
      // of it never round-trip HBM, and one dispatch is saved
      int k_out = c.out.empty() ? 1 : shards_of(c.out[0], mesh);
      double bnd = 2.0 * (double)n.output_bytes(0) / k_out / m.hbm_bw;
      nc.fwd = std::max(nc.fwd - bnd - m.min_op_time, floor_f);
      if (training)
        nc.bwd = std::max(nc.bwd - bnd, floor_b);
    }
  }
  if (training && c.remat)
    // rematerialization: the backward pass first re-runs this op's
    // forward from its checkpointed inputs. Applied after the per-impl
    // delta so the recompute prices the chosen lowering; nc.src stays
    // whatever priced fwd (cost_source provenance intact).
    nc.bwd += nc.fwd;
  if (c.psum_bytes > 0 && c.psum_k > 1) {
    double t = m.allreduce_time(c.psum_bytes, c.psum_k, c.psum_axis);
    nc.comm = training ? 2.0 * t : t;  // bwd mirrors the collective
  }
  if (training && c.bwd_psum_bytes > 0 && c.psum_k > 1)
    // backward-only partial-sum all-reduce (col-parallel dX, replicated
    // scatter gradients, tiny-batch weight-grad movement)
    nc.comm += m.allreduce_time(c.bwd_psum_bytes, c.psum_k, c.psum_axis);
  if (c.wgather_bytes > 0 && c.psum_k > 1)
    // forward-only weight all-gather (tiny-batch row lowering) — charged
    // once; its backward counterpart is the bwd_psum weight-grad AR
    nc.comm += m.allgather_time(c.wgather_bytes, c.psum_k, c.psum_axis);
  if (c.ring_bytes > 0 && c.ring_k > 1) {
    // ring attention K/V rotation; the backward rotates K/V and dK/dV
    double t = m.ring_time(c.ring_bytes, c.ring_k, kSeq);
    nc.comm += training ? 3.0 * t : t;
  }
  if (c.gather_bytes > 0 && c.gather_k > 1) {
    double t = m.allgather_time(c.gather_bytes, c.gather_k, c.gather_axis);
    nc.comm += training ? 2.0 * t : t;  // bwd scatters the gradient back
  }
  if (training && c.gradsync_bytes > 0 && c.gradsync_k > 1) {
    int spans = slices_spanned(mesh, m);
    double sync;
    if (c.wus)
      // WUS: reduce-scatter the gradients, update shard-locally, then
      // all-gather the updated (bf16) compute params — roughly the
      // all-reduce's wire bytes, but the optimizer update and its state
      // shrink by gradsync_k (node_param_memory / the simulator's
      // update-traffic term), which is where WUS wins.
      sync = m.wus_rs_time(c.gradsync_bytes, c.gradsync_k, spans, kData) +
             m.wus_ag_time(c.gradsync_bytes, c.gradsync_k, spans, kData);
    else
      sync = m.hier_allreduce_time(c.gradsync_bytes, c.gradsync_k, spans,
                                   kData);
    if (c.ovl) {
      // latency hiding: the bucketed async sync hides under the overlap
      // window the DP already prices for this op — its backward compute
      // (early buckets' collectives ride under the rest of backward)
      // plus, when the update-triad term is being priced, the optimizer
      // fusion tail the WUS param all-gather prefetches under.
      double hide = nc.bwd +
                    update_triad_time(n, c, mesh, m, opt_state_factor);
      OverlapPricing ov = overlap_price(
          m, sync, c.gradsync_bytes * m.comm_bytes_factor, hide);
      nc.gradsync = ov.exposed;
      nc.gradsync_hidden = ov.hidden;
      nc.ovl_bucket_mb = ov.bucket_mb;
      nc.ovl_buckets = ov.buckets;
    } else {
      nc.gradsync = sync;
    }
  }
  if (training)
    nc.gradsync += update_triad_time(n, c, mesh, m, opt_state_factor);
  return nc;
}

// Per-device parameter (+optimizer-state) bytes of a node under a choice —
// permanent for the whole iteration.
inline double node_param_memory(const Node& n, const Choice& c,
                                const MeshShape& mesh,
                                double opt_state_factor) {
  if (is_view_op(n.type)) return 0.0;
  double factor = 1.0 + opt_state_factor;
  if (c.wus && c.gradsync_k > 1)
    // weight-update sharding: the optimizer moments (and the f32 master
    // they update) shard over the gradient ring; only the compute-param
    // copy stays replicated
    factor = 1.0 + opt_state_factor / c.gradsync_k;
  return detail::sharded_param_bytes(n, c, mesh) * factor;
}

// Per-device activation bytes a node's outputs occupy while live.
inline double node_act_bytes(const Node& n, const Choice& c,
                             const MeshShape& mesh) {
  if (is_view_op(n.type)) return 0.0;  // fused away: materializes nothing
  if (c.remat) return 0.0;  // "_r": the output is not a saved residual —
                            // backward rebuilds it from the checkpointed
                            // inputs (counted at their producers)
  double mem = 0;
  for (size_t i = 0; i < n.own_outputs(); ++i) {
    int k = i < c.out.size() ? shards_of(c.out[i], mesh) : 1;
    mem += n.act_bytes(i) / k;
  }
  // what an op with a wide interior keeps for its backward pass besides
  // its outputs (the scan's projections and chunk states, the experts'
  // buffer): the op states it, and it shards as the first output does
  double interior = n.act_interior_bytes();
  if (interior > 0 && !c.out.empty())
    mem += interior / shards_of(c.out[0], mesh);
  return mem;
}

// Per-device memory of a node under a choice: sharded params (+optimizer
// state) + sharded activations. Under training every activation is a
// saved-for-backward residual, so the whole-graph sum IS the backward-start
// peak; inference uses the liveness-aware accounting in the DP/simulator
// instead (reference bump-allocator role, simulator.h:699-700).
inline double node_memory(const Node& n, const Choice& c, const MeshShape& mesh,
                          double opt_state_factor) {
  return node_param_memory(n, c, mesh, opt_state_factor) +
         node_act_bytes(n, c, mesh);
}

}  // namespace ffsearch
