// Event-driven taskgraph simulator.
//
// Analog of the reference's Simulator::simulate_runtime
// (src/runtime/simulator.cc:822-900): build a SimTask DAG for one training
// iteration — forward per op, backward per op (reverse order), resharding
// collectives on edges, partial-sum collectives, per-parameter gradient
// all-reduce, optimizer update — then list-schedule it on two streams per
// chip (compute, ICI) reflecting how XLA overlaps async collectives with
// compute. SPMD symmetry means one chip's schedule is the iteration time.
//
// The reference's `search_overlap_backward_update` flag (config.h:130)
// maps to `overlap`: when false, gradient all-reduces wait for the whole
// backward pass (no overlap), as in its default Legion schedule.
#pragma once

#include <algorithm>
#include <cstdint>
#include <map>
#include <set>
#include <string>
#include <vector>

#include "ffs_graph.hpp"
#include "ffs_machine.hpp"
#include "ffs_strategy.hpp"

namespace ffsearch {

struct SimTask {
  enum class Kind { Fwd, Bwd, Comm, GradSync, Update };
  Kind kind;
  int node_idx = -1;  // -1 for Update
  double duration = 0;
  std::vector<int> deps;  // indices into task vector
  // collective detail (Comm/GradSync): what the cost charges, so the
  // priced set can be diffed against the collectives XLA actually emits
  // (SURVEY §7 hard-part 3; tests/test_collective_validation.py)
  std::string collective;  // "allreduce"|"allgather"|"ppermute"|"reshard"|""
  double bytes = 0;        // global payload bytes priced
  // filled by the scheduler:
  double start = 0, finish = 0;
  // seconds of this comm-stream task that ran while the compute stream
  // was busy — the predicted-hidden interval the simtrace sim: lanes
  // surface (filled by the post-schedule pass; 0 for compute tasks)
  double hidden = 0;
};

struct SimResult {
  double iteration_time = 0;
  double fwd_time = 0, bwd_time = 0, comm_time = 0, gradsync_time = 0;
  // total comm/gradsync seconds hidden under compute in the schedule
  // (plus the pipeline/"_ovl" analytic hidden terms) — the predicted
  // twin of the devtrace's measured overlapped_comms_s
  double hidden_comm_time = 0;
  double memory = 0;  // per-device bytes
  std::vector<SimTask> tasks;  // schedule (for --taskgraph export)
};

class TaskgraphSimulator {
 public:
  TaskgraphSimulator(const Graph& g, const MachineModel& m, const MeshShape& mesh,
                     bool training = true, bool overlap = true,
                     double opt_state_factor = 2.0,
                     const MeasuredCosts* measured = nullptr)
      : g_(g), m_(m), mesh_(mesh), training_(training), overlap_(overlap),
        opt_state_factor_(opt_state_factor), measured_(measured) {}

  // `assign[i]` = chosen Choice for g_.nodes[i].
  SimResult simulate(const std::vector<Choice>& assign) const {
    const size_t N = g_.nodes.size();
    std::vector<SimTask> tasks;
    std::vector<int> fwd_id(N, -1), bwd_id(N, -1);
    auto add = [&](SimTask t) {
      tasks.push_back(std::move(t));
      return static_cast<int>(tasks.size()) - 1;
    };

    SimResult res;
    // liveness accounting (inference): an activation frees at its last
    // consumer; track the peak instead of the sum (reference
    // bump-allocator role, simulator.h:699-700). Training keeps the sum:
    // every activation is a saved-for-backward residual.
    std::map<std::pair<int64_t, int>, size_t> last_use;
    if (!training_)
      for (size_t i = 0; i < N; ++i)
        for (const EdgeRef& e : g_.nodes[i].inputs)
          if (e.src_guid >= 0) last_use[{e.src_guid, e.src_idx}] = i;
    double act_live = 0, act_peak = 0;
    // ---- forward + edge reshard tasks ----
    for (size_t i = 0; i < N; ++i) {
      const Node& n = g_.nodes[i];
      const Choice& c = assign[i];
      NodeCost nc = node_cost(n, c, mesh_, m_, training_, measured_);
      std::vector<int> deps;
      for (size_t slot = 0; slot < n.inputs.size(); ++slot) {
        const EdgeRef& e = n.inputs[slot];
        if (e.src_guid < 0) continue;
        int pi = g_.index_of.at(e.src_guid);
        const Choice& pc = assign[pi];
        const Spec& prod = pc.out[e.src_idx];
        const Spec& need = slot < c.in.size() ? c.in[slot]
                                              : rep_spec(prod.size());
        double rb = reshard_cost(prod, need,
                                 (double)g_.nodes[pi].output_bytes(e.src_idx),
                                 mesh_, m_);
        if (rb > 0) {
          SimTask ct{SimTask::Kind::Comm, (int)i, rb, {fwd_id[pi]},
                     "reshard",
                     (double)g_.nodes[pi].output_bytes(e.src_idx)};
          deps.push_back(add(std::move(ct)));
          res.comm_time += rb;
        } else {
          deps.push_back(fwd_id[pi]);
        }
      }
      SimTask ft{SimTask::Kind::Fwd, (int)i, nc.fwd, deps, "", 0};
      fwd_id[i] = add(std::move(ft));
      res.fwd_time += nc.fwd;
      if (c.psum_bytes > 0 && c.psum_k > 1) {
        double t = m_.allreduce_time(c.psum_bytes, c.psum_k, c.psum_axis);
        SimTask ct{SimTask::Kind::Comm, (int)i, t, {fwd_id[i]},
                   "allreduce", c.psum_bytes};
        fwd_id[i] = add(std::move(ct));  // consumers wait on the psum
        res.comm_time += t;
      }
      if (c.ring_bytes > 0 && c.ring_k > 1) {
        // ring-attention K/V rotation (seq axis): runs on the ICI stream
        double t = m_.ring_time(c.ring_bytes, c.ring_k, kSeq);
        SimTask ct{SimTask::Kind::Comm, (int)i, t, {fwd_id[i]},
                   "ppermute", c.ring_bytes};
        fwd_id[i] = add(std::move(ct));
        res.comm_time += t;
      }
      if (c.gather_bytes > 0 && c.gather_k > 1) {
        // all-gather a Combine boundary forces
        double t = m_.allgather_time(c.gather_bytes, c.gather_k, c.gather_axis);
        SimTask ct{SimTask::Kind::Comm, (int)i, t, {fwd_id[i]},
                   "allgather", c.gather_bytes};
        fwd_id[i] = add(std::move(ct));
        res.comm_time += t;
      }
      if (c.wgather_bytes > 0 && c.psum_k > 1) {
        // tiny-batch row lowering: the kernel all-gathers once forward
        double t = m_.allgather_time(c.wgather_bytes, c.psum_k, c.psum_axis);
        SimTask ct{SimTask::Kind::Comm, (int)i, t, {fwd_id[i]},
                   "allgather", c.wgather_bytes};
        fwd_id[i] = add(std::move(ct));
        res.comm_time += t;
      }
      res.memory += node_param_memory(n, c, mesh_, opt_state_factor_);
      if (training_) {
        res.memory += node_act_bytes(n, c, mesh_);
      } else {
        act_live += node_act_bytes(n, c, mesh_);
        act_peak = std::max(act_peak, act_live);
        // inputs whose last consumer is this node free now. A view op
        // aliases its input, so consumption through a view conservatively
        // never frees (overcounts slightly rather than undercounting).
        for (const EdgeRef& e : is_view_op(n.type)
                                    ? std::vector<EdgeRef>{} : n.inputs) {
          if (e.src_guid < 0) continue;
          auto lu = last_use.find({e.src_guid, e.src_idx});
          if (lu != last_use.end() && lu->second == i) {
            int pi = g_.index_of.at(e.src_guid);
            const Choice& pc = assign[pi];
            int k = e.src_idx < (int)pc.out.size()
                        ? shards_of(pc.out[e.src_idx], mesh_) : 1;
            act_live -=
                (double)g_.nodes[pi].output_bytes(e.src_idx) / k;
            last_use.erase(lu);  // free once even with multi-input reuse
          }
        }
      }
    }
    if (!training_) res.memory += act_peak;

    if (training_) {
      // ---- backward (reverse topo): bwd_i after bwd of all consumers ----
      for (int i = static_cast<int>(N) - 1; i >= 0; --i) {
        const Node& n = g_.nodes[i];
        const Choice& c = assign[i];
        NodeCost nc = node_cost(n, c, mesh_, m_, true, measured_);
        std::vector<int> deps = {fwd_id[i]};
        auto it = g_.consumers.find(n.guid);
        if (it != g_.consumers.end())
          for (const auto& cons : it->second)
            if (bwd_id[cons.first] >= 0) deps.push_back(bwd_id[cons.first]);
        double bwd_comm_bytes = 0;
        double dur = nc.bwd;
        if (c.psum_k > 1 && c.psum_bytes > 0) {
          dur += m_.allreduce_time(c.psum_bytes, c.psum_k, c.psum_axis);
          bwd_comm_bytes += c.psum_bytes;
        }
        if (c.psum_k > 1 && c.bwd_psum_bytes > 0) {
          // backward-only partial-sum AR (col-parallel dX, replicated
          // scatter grads, tiny-batch weight-grad movement)
          dur += m_.allreduce_time(c.bwd_psum_bytes, c.psum_k, c.psum_axis);
          bwd_comm_bytes += c.bwd_psum_bytes;
        }
        if (c.ring_bytes > 0 && c.ring_k > 1)  // bwd rotates K/V and dK/dV
          dur += 2.0 * m_.ring_time(c.ring_bytes, c.ring_k, kSeq);
        SimTask bt{SimTask::Kind::Bwd, i, dur, deps,
                   bwd_comm_bytes > 0 ? "allreduce" : "", bwd_comm_bytes};
        bwd_id[i] = add(std::move(bt));
        res.bwd_time += dur;
      }
      // ---- per-parameter gradient sync + optimizer update ----
      std::vector<int> sync_ids;
      int last_bwd = N > 0 ? bwd_id[0] : -1;
      // reverse node order = backward-completion order: the scheduler
      // below assigns the comm stream in task-creation order, and a real
      // runtime fires each parameter's all-reduce the moment its backward
      // finishes (head layers first) — creation order must match or the
      // simulated syncs all queue behind the one that is ready last
      int spans = slices_spanned(mesh_, m_);
      for (size_t j = 0; j < N; ++j) {
        size_t i = N - 1 - j;
        const Choice& c = assign[i];
        if (c.gradsync_bytes > 0 && c.gradsync_k > 1) {
          std::vector<int> deps = {bwd_id[i]};
          // "_ovl": the executor issues this op's sync as bucketed async
          // collectives the moment its grads exist — never serialized
          // behind the whole backward, even under the no-overlap default
          // schedule. The per-bucket launch overhead is charged on the
          // task (hiding is not free); the hiding itself emerges from
          // the two-stream list schedule and is reported by the
          // post-schedule hidden pass below.
          if (!c.ovl && !overlap_ && last_bwd >= 0)
            deps.push_back(last_bwd);
          double wire = c.gradsync_bytes * m_.comm_bytes_factor;
          double bwd_dur = tasks[bwd_id[i]].duration;
          if (c.wus) {
            // WUS: reduce-scatter the gradients (the RS half keeps the
            // census 'allreduce' bucket — XLA's AR decomposition), then
            // all-gather the updated compute params. Priced as two
            // tasks so the collective census diff sees both kinds.
            double t1 = m_.wus_rs_time(c.gradsync_bytes, c.gradsync_k,
                                       spans, kData);
            double t2 = m_.wus_ag_time(c.gradsync_bytes, c.gradsync_k,
                                       spans, kData);
            if (c.ovl)
              t1 += overlap_price(m_, t1 + t2, wire, bwd_dur).buckets *
                    m_.collective_launch_overhead;
            SimTask rs{SimTask::Kind::GradSync, (int)i, t1, deps,
                       "allreduce", c.gradsync_bytes};
            int rs_id = add(std::move(rs));
            SimTask ag{SimTask::Kind::GradSync, (int)i, t2, {rs_id},
                       "allgather", c.gradsync_bytes};
            sync_ids.push_back(add(std::move(ag)));
            res.gradsync_time += t1 + t2;
          } else {
            double t = m_.hier_allreduce_time(c.gradsync_bytes,
                                              c.gradsync_k, spans, kData);
            if (c.ovl)
              t += overlap_price(m_, t, wire, bwd_dur).buckets *
                   m_.collective_launch_overhead;
            SimTask st{SimTask::Kind::GradSync, (int)i, t, deps,
                       "allreduce", c.gradsync_bytes};
            sync_ids.push_back(add(std::move(st)));
            res.gradsync_time += t;
          }
        }
      }
      // optimizer update traffic: read p + read g + write p (3x params)
      // plus read+write of each optimizer-state copy (2x per copy;
      // opt_state_factor = state copies: 0 plain SGD, 1 momentum, 2 Adam).
      // Bandwidth: the measured update-triad rate when profiled
      // ("__update_bw__" — elementwise updates run well below the
      // datasheet HBM figure), else the analytic hbm_bw.
      double upd_bw = m_.hbm_bw;
      if (measured_) {
        auto it = measured_->find("__update_bw__");
        if (it != measured_->end() && it->second > 0) upd_bw = it->second;
      }
      double upd_bytes = 0, upd_saved = 0;
      for (size_t i = 0; i < N; ++i) {
        // WUS: the update triad runs on the per-chip shard only —
        // optimizer HBM traffic divides by the gradient-ring size.
        // "_k:fused" choices price the one-dispatch fused region: one
        // param round trip fewer and two launches saved, CAPPED at the
        // node's own update time (mirrors update_triad_time's per-node
        // floor, ffs_strategy.hpp — a tiny fused op must not let its
        // launch saving eat into other ops' update traffic, or the
        // replay would price fused cheaper than the DP did).
        const Choice& c = assign[i];
        double div = (c.wus && c.gradsync_k > 1) ? (double)c.gradsync_k
                                                 : 1.0;
        double copies = (c.kernel == "fused") ? 2.0 : 3.0;
        double nb = (double)g_.nodes[i].param_bytes() *
                    (copies + 2.0 * opt_state_factor_) / div;
        upd_bytes += nb;
        if (c.kernel == "fused" && g_.nodes[i].param_bytes() > 0)
          upd_saved += std::min(2.0 * m_.collective_launch_overhead,
                                nb / upd_bw);
      }
      std::vector<int> deps = sync_ids;
      if (last_bwd >= 0) deps.push_back(last_bwd);
      SimTask ut{SimTask::Kind::Update, -1,
                 std::max(0.0, upd_bytes / upd_bw - upd_saved), deps, "",
                 0};
      add(std::move(ut));
    }

    // ---- list schedule on {compute, comm} streams ----
    double compute_free = 0, comm_free = 0, makespan = 0;
    for (auto& t : tasks) {
      double ready = 0;
      for (int d : t.deps)
        if (d >= 0) ready = std::max(ready, tasks[d].finish);
      bool on_comm = t.kind == SimTask::Kind::Comm ||
                     t.kind == SimTask::Kind::GradSync;
      double& stream = on_comm ? comm_free : compute_free;
      t.start = std::max(ready, stream);
      t.finish = t.start + t.duration;
      stream = t.finish;
      makespan = std::max(makespan, t.finish);
    }
    // post-schedule hidden pass: seconds of each comm-stream task that
    // ran while the compute stream was busy — the predicted hidden
    // intervals (compute tasks are sequential on one stream, so their
    // [start, finish) spans are disjoint and sorted)
    {
      std::vector<std::pair<double, double>> busy;
      for (const auto& t : tasks)
        if (t.kind != SimTask::Kind::Comm &&
            t.kind != SimTask::Kind::GradSync && t.duration > 0)
          busy.push_back({t.start, t.finish});
      size_t lo = 0;
      for (auto& t : tasks) {
        if (t.kind != SimTask::Kind::Comm &&
            t.kind != SimTask::Kind::GradSync)
          continue;
        double h = 0;
        while (lo < busy.size() && busy[lo].second <= t.start) ++lo;
        for (size_t b = lo; b < busy.size() && busy[b].first < t.finish;
             ++b)
          h += std::max(0.0, std::min(t.finish, busy[b].second) -
                                 std::max(t.start, busy[b].first));
        t.hidden = h;
        res.hidden_comm_time += h;
      }
    }
    res.iteration_time = makespan;
    if (measured_) {
      // fixed per-step dispatch/runtime cost measured on the live device
      // (program launch + host runtime)
      auto it = measured_->find("__step_overhead__");
      if (it != measured_->end()) res.iteration_time += it->second;
    }
    res.tasks = std::move(tasks);
    return res;
  }

 private:
  const Graph& g_;
  const MachineModel& m_;
  MeshShape mesh_;
  bool training_;
  bool overlap_;
  double opt_state_factor_;
  const MeasuredCosts* measured_;
};

// ---- GPipe pipeline simulation (pp > 1 meshes) ----------------------------

// Repeated-block metadata detected by the Python side
// (flexflow_tpu/parallel/pipeline_detect.py) and shipped in the request.
struct PipelineMeta {
  bool present = false;
  int num_blocks = 0;
  std::set<int64_t> body, head, tail;
  double block_out_bytes = 0;
  int64_t batch = 0;
};

// Iteration time of the graph run as a pp-stage pipeline with M
// microbatches, per-node inner choices `assign` (computed by the frontier
// DP on the inner dp-only mesh). Model (parallel/pipeline.py semantics):
//   * `circular=false` (GPipe): stages hold k = num_blocks/pp consecutive
//     blocks and run all of them per tick; T = M + pp - 1 ticks (bubble
//     (pp-1)/T). `circular=true`: blocks assign round-robin, one block
//     per tick, each microbatch circulates k rounds; T = kM + pp - 1
//     ticks (bubble (pp-1)/(kM+pp-1)) — the schedule is a PRICED
//     dimension, as are M (swept over the divisor lattice of batch/dp by
//     the caller) and the per-op "_wus" gradient-sync twins;
//   * each tick ppermutes the microbatch activation one hop (bwd: the
//     returning gradient too); the sharded microbatch queue
//     (`shard_queue`, the runtime default when pp | M) adds two
//     single-microbatch ppermute streams per tick plus pp-1 drain hops;
//   * head/tail ops run outside the pipeline on the full batch;
//   * stage weights shard 1/pp: gradient sync, optimizer update and
//     parameter memory divide by pp; a body/head choice with `wus` prices
//     its sync as reduce-scatter + all-gather with the update triad and
//     optimizer-state memory divided by the gradient ring;
//   * queue memory: 2x the body boundary tensor over dp, divided by pp
//     when the queue is sharded; the circular schedule adds a stage-0
//     recirculation buffer (one boundary tensor over dp).
// `res.tasks` carries zero-duration census records (collective, bytes) so
// strategy replays (ffs_simulate) can diff priced vs inferred/emitted
// collectives on pipe meshes too.
// `body_remat` prices block-body rematerialization (the pipeline face of
// the "_r" dimension, ISSUE 20): the stage checkpoints each block
// instance's boundary input and recomputes the block interior in
// backward — backward ticks gain one forward tick of recompute, and the
// body residual term shrinks from every interior activation to the
// per-block boundaries (~1/block-depth). Swept as a candidate dimension
// by eval_graph alongside M and the schedule.
inline SimResult simulate_pipeline(const Graph& g, const MachineModel& m,
                                   const MeshShape& mesh,
                                   const std::vector<Choice>& assign,
                                   const PipelineMeta& meta, bool training,
                                   double opt_state_factor,
                                   const MeasuredCosts* measured, int M,
                                   bool circular = false,
                                   bool shard_queue = true,
                                   bool body_remat = false) {
  SimResult res;
  const int pp = mesh.pp;
  const int k = pp > 0 ? std::max(1, meta.num_blocks / pp) : 1;
  const int rounds = circular ? k : 1;
  const bool qshard = shard_queue && pp > 0 && M % pp == 0;
  double fwd_body = 0, bwd_body = 0, fwd_edge = 0;
  double body_act = 0, body_param_mem = 0;
  // body gradient-sync bytes, split by (wus, ovl): the "_ovl" groups
  // price only the un-hidden tail of their sync (the stacked body grads
  // finish with the last backward tick, so the hiding window is the
  // optimizer-fusion tail, not backward compute)
  double body_gs_plain = 0, body_gs_wus = 0;
  double body_gs_plain_ovl = 0, body_gs_wus_ovl = 0;
  int body_ops = 0;
  int gradsync_k = mesh.dp;
  double ht_time = 0, ht_param_mem = 0, ht_act = 0, ht_gradsync = 0;
  double upd_bytes = 0, upd_saved = 0;
  // update-triad bandwidth (measured override when profiled) — hoisted
  // above the node loop so the per-node fused launch-saving cap below
  // can price each node's own update time
  double upd_bw = m.hbm_bw;
  if (measured != nullptr) {
    auto it = measured->find("__update_bw__");
    if (it != measured->end() && it->second > 0) upd_bw = it->second;
  }
  MeshShape inner = mesh;
  inner.pp = 1;
  const int spans = slices_spanned(inner, m);
  const double mem_f = training ? opt_state_factor : 0.0;
  auto add_task = [&](SimTask::Kind kind, int node, double dur,
                      const char* coll, double bytes) {
    res.tasks.push_back(SimTask{kind, node, dur, {}, coll, bytes});
  };
  for (size_t i = 0; i < g.nodes.size(); ++i) {
    const Node& n = g.nodes[i];
    const Choice& c = assign[i];
    NodeCost nc = node_cost(n, c, inner, m, training, measured);
    double pmem = node_param_memory(n, c, inner, mem_f);
    double act = 0;
    for (size_t oi = 0; oi < n.own_outputs(); ++oi)
      act += n.act_bytes(oi) /
             (oi < c.out.size() ? shards_of(c.out[oi], inner) : 1);
    const bool body = meta.body.count(n.guid) > 0;
    if (body) {
      fwd_body += nc.fwd;
      bwd_body += nc.bwd;
      fwd_edge += nc.comm;
      body_param_mem += pmem;
      body_act += act;
      if (training && c.gradsync_bytes > 0 && c.gradsync_k > 1)
        (c.ovl ? (c.wus ? body_gs_wus_ovl : body_gs_plain_ovl)
               : (c.wus ? body_gs_wus : body_gs_plain)) +=
            c.gradsync_bytes;
      if (!is_view_op(n.type)) ++body_ops;
    } else {
      ht_time += nc.fwd + nc.bwd + nc.comm;
      ht_param_mem += pmem;
      ht_act += act;
      if (training && c.gradsync_bytes > 0 && c.gradsync_k > 1) {
        double t;
        if (c.wus) {
          t = m.wus_rs_time(c.gradsync_bytes, c.gradsync_k, spans, kData) +
              m.wus_ag_time(c.gradsync_bytes, c.gradsync_k, spans, kData);
          add_task(SimTask::Kind::GradSync, (int)i, 0, "allreduce",
                   c.gradsync_bytes);
          add_task(SimTask::Kind::GradSync, (int)i, 0, "allgather",
                   c.gradsync_bytes);
        } else {
          t = m.hier_allreduce_time(c.gradsync_bytes, c.gradsync_k, spans,
                                    kData);
          add_task(SimTask::Kind::GradSync, (int)i, 0, "allreduce",
                   c.gradsync_bytes);
        }
        if (c.ovl) {
          // head/tail op outside the pipeline: its bucketed async sync
          // hides under the op's own backward compute, as in node_cost
          OverlapPricing ov = overlap_price(
              m, t, c.gradsync_bytes * m.comm_bytes_factor, nc.bwd);
          res.hidden_comm_time += ov.hidden;
          t = ov.exposed;
        }
        ht_gradsync += t;
      }
    }
    if (training && n.param_bytes() > 0) {
      // optimizer update-triad HBM traffic: stage weights already /pp;
      // WUS additionally divides by the gradient ring; "_k:fused"
      // choices price the one-dispatch fused region with the launch
      // saving capped at the node's own update time (update_triad_time)
      double div = (c.wus && c.gradsync_k > 1) ? (double)c.gradsync_k : 1.0;
      double copies = (c.kernel == "fused") ? 2.0 : 3.0;
      double nb = detail::sharded_param_bytes(n, c, inner) /
                  (body ? (double)pp : 1.0) *
                  (copies + 2.0 * opt_state_factor) / div;
      upd_bytes += nb;
      if (c.kernel == "fused")
        upd_saved += std::min(2.0 * m.collective_launch_overhead,
                              nb / upd_bw);
    }
    // per-op collective census records (durations already in nc.comm)
    double psum_total = (training ? 2.0 : 1.0) * c.psum_bytes +
                        (training ? c.bwd_psum_bytes : 0.0);
    if (psum_total > 0 && c.psum_k > 1)
      add_task(SimTask::Kind::Comm, (int)i, 0, "allreduce", psum_total);
    if (c.gather_bytes > 0 && c.gather_k > 1)
      add_task(SimTask::Kind::Comm, (int)i, 0, "allgather",
               (training ? 2.0 : 1.0) * c.gather_bytes);
    if (c.wgather_bytes > 0 && c.psum_k > 1)
      add_task(SimTask::Kind::Comm, (int)i, 0, "allgather",
               c.wgather_bytes);
    if (c.ring_bytes > 0 && c.ring_k > 1)
      add_task(SimTask::Kind::Comm, (int)i, 0, "ppermute",
               (training ? 3.0 : 1.0) * c.ring_bytes);
  }
  const double ticks = (double)rounds * M + pp - 1;
  // per-tick stage compute, floored by the per-op dispatch minimum of the
  // ops one stage executes per microbatch per tick (one block's worth
  // under the circular schedule, k blocks' worth under GPipe)
  double op_floor = (double)body_ops / (pp * rounds) * m.min_op_time;
  double tick_fwd = std::max(fwd_body / ((double)pp * rounds * M), op_floor);
  double tick_bwd = std::max(bwd_body / ((double)pp * rounds * M), op_floor);
  if (training && body_remat)
    // block-body remat: every backward tick first re-runs the block's
    // forward from its checkpointed boundary input
    tick_bwd += tick_fwd;
  // activation hop: boundary tensor / (M * dp) per microbatch shard.
  // Each tick, every stage forwards simultaneously, so the tick's hop
  // cost is the slowest hop: if the pipeline's chip range extends past
  // one slice, at least one stage boundary crosses DCN, and that hop
  // gates the tick — price all ticks' hops at DCN in that case
  // (enumerate_meshes allows pipe stages to span slices).
  double hop_bytes = meta.block_out_bytes * m.comm_bytes_factor /
                     ((double)M * mesh.dp);
  int inner_chips = mesh.dp * mesh.mp * mesh.sp * mesh.ep;
  bool spans_slices =
      m.num_slices > 1 && inner_chips * pp > m.chips_per_slice();
  double hop1 = spans_slices ? (m.dcn_latency + hop_bytes / m.dcn_bw)
                             : (m.ici_latency + hop_bytes / m.ici_bw);
  // sharded queue: the input and output streams are two more
  // single-microbatch ppermutes riding the ring every tick, plus pp-1
  // output-drain hops after the last compute tick. The streams are
  // prefetch/writeback traffic (their payload is consumed S-1 ticks
  // later), so they overlap compute and charge bandwidth only; the
  // activation hop stays on the critical path with its latency.
  double stream_bw = spans_slices ? m.dcn_bw : m.ici_bw;
  double hop = hop1 + (qshard ? 2.0 * hop_bytes / stream_bw : 0.0);
  double drain = qshard ? (pp - 1) * hop1 : 0.0;
  add_task(SimTask::Kind::Comm, -1, 0, "ppermute",
           (ticks * (qshard ? 3.0 : 1.0) + (qshard ? pp - 1 : 0)) *
               meta.block_out_bytes / ((double)M * mesh.dp) *
               (training ? 2.0 : 1.0));
  res.fwd_time = ticks * (tick_fwd + hop) + drain + fwd_edge;
  res.comm_time = ticks * hop * (training ? 2.0 : 1.0) + drain + fwd_edge;
  // fwd_edge (per-op collectives of body choices) charges iteration_time
  // too — pp>1 meshes must not be costed comm-free vs the taskgraph sim
  res.iteration_time =
      ht_time + ticks * (tick_fwd + hop) + drain + fwd_edge;
  if (training) {
    res.bwd_time = ticks * (tick_bwd + hop);
    res.iteration_time += res.bwd_time;
    double upd_time = std::max(0.0, upd_bytes / upd_bw - upd_saved);
    if (mesh.dp > 1 && body_gs_plain > 0) {
      double t = m.hier_allreduce_time(body_gs_plain / pp, gradsync_k,
                                       spans, kData);
      res.gradsync_time += t;
      add_task(SimTask::Kind::GradSync, -1, t, "allreduce",
               body_gs_plain / pp);
    }
    if (mesh.dp > 1 && body_gs_wus > 0) {
      // WUS twins under the pipeline: reduce-scatter the stage-sharded
      // body grads over the data ring, all-gather the updated compute
      // params — both on bytes/pp (the stage's stacked slice)
      double t1 = m.wus_rs_time(body_gs_wus / pp, gradsync_k, spans, kData);
      double t2 = m.wus_ag_time(body_gs_wus / pp, gradsync_k, spans, kData);
      res.gradsync_time += t1 + t2;
      add_task(SimTask::Kind::GradSync, -1, t1, "allreduce",
               body_gs_wus / pp);
      add_task(SimTask::Kind::GradSync, -1, t2, "allgather",
               body_gs_wus / pp);
    }
    if (mesh.dp > 1 && body_gs_plain_ovl + body_gs_wus_ovl > 0) {
      // "_ovl" body groups: the stacked body grads only finish with the
      // last backward tick (grad accumulation over microbatches), so
      // the hiding window is the optimizer-fusion tail — the update
      // triad the WUS param all-gather prefetches under — not backward
      // compute. Census bytes are recorded unchanged; only the priced
      // exposed time shrinks.
      double hide = upd_time;
      if (body_gs_plain_ovl > 0) {
        double t = m.hier_allreduce_time(body_gs_plain_ovl / pp,
                                         gradsync_k, spans, kData);
        OverlapPricing ov = overlap_price(
            m, t, body_gs_plain_ovl / pp * m.comm_bytes_factor, hide);
        hide = std::max(0.0, hide - ov.hidden);
        res.gradsync_time += ov.exposed;
        res.hidden_comm_time += ov.hidden;
        add_task(SimTask::Kind::GradSync, -1, ov.exposed, "allreduce",
                 body_gs_plain_ovl / pp);
      }
      if (body_gs_wus_ovl > 0) {
        double t =
            m.wus_rs_time(body_gs_wus_ovl / pp, gradsync_k, spans, kData) +
            m.wus_ag_time(body_gs_wus_ovl / pp, gradsync_k, spans, kData);
        OverlapPricing ov = overlap_price(
            m, t, body_gs_wus_ovl / pp * m.comm_bytes_factor, hide);
        res.gradsync_time += ov.exposed;
        res.hidden_comm_time += ov.hidden;
        add_task(SimTask::Kind::GradSync, -1, ov.exposed, "allreduce",
                 body_gs_wus_ovl / pp);
        add_task(SimTask::Kind::GradSync, -1, 0, "allgather",
                 body_gs_wus_ovl / pp);
      }
    }
    res.gradsync_time += ht_gradsync;
    res.iteration_time += res.gradsync_time;
    res.iteration_time += upd_time;
  }
  if (measured != nullptr) {
    auto it = measured->find("__step_overhead__");
    if (it != measured->end()) res.iteration_time += it->second;
  }
  // queue + output buffer: replicated over pipe in the fallback lowering,
  // sharded 1/pp otherwise (plus the in/out stream microbatches); the
  // circular schedule keeps a stage-0 recirculation buffer windowed to
  // the M-pp+1 in-flight slots in BOTH lowerings (a value banked at tick
  // v+pp-1 is consumed at tick v+M, so at most M-pp+1 slots are ever
  // live — parallel/pipeline.py's ring buffer, data-sharded over dp)
  double queue_mem =
      2.0 * meta.block_out_bytes / mesh.dp / (qshard ? pp : 1);
  if (rounds > 1)
    queue_mem += meta.block_out_bytes / mesh.dp * (double)(M - pp + 1) / M;
  if (qshard)
    queue_mem += 3.0 * meta.block_out_bytes / ((double)M * mesh.dp);
  double body_act_eff = body_act / pp;
  if (training && body_remat && meta.block_out_bytes > 0)
    // block-body remat residuals: k*M boundary slots of
    // block_out/(M*dp) each per stage (= k*block_out/dp), plus the one
    // block interior transiently rebuilt during the current backward
    // tick — instead of every interior activation of the stage's blocks
    body_act_eff = (double)k * meta.block_out_bytes / mesh.dp +
                   body_act / ((double)meta.num_blocks * M);
  res.memory = body_param_mem / pp + ht_param_mem +
               (training ? body_act_eff + ht_act : 0.0) + queue_mem;
  return res;
}

}  // namespace ffsearch
