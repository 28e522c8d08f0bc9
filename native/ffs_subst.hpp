// Graph-substitution engine: TASO/Unity-style pattern->replacement rewrites.
//
// Native analog of the reference's GraphXfer machinery: backtracking
// pattern match + apply (src/runtime/substitution.cc:596 GraphXfer::run),
// the hand-written substitution generators (:1726-1860), and the
// machine-generated rule corpus loader (src/runtime/substitution_loader.cc,
// substitutions/graph_subst_3_v2.json: 640 rules).
//
// A rule is a source pattern graph and a replacement graph over the same
// external inputs, with an output mapping. Matching binds pattern ops to
// graph nodes (types, edges, and parameter constraints must agree;
// parameters may be wildcards bound consistently across the pattern).
// Application splices the replacement in with fresh guids, re-inferring
// shapes locally — an application whose shapes don't check out is
// discarded, which also filters reference rules whose replica-dim
// conventions don't hold in this framework's explicit-shape form.
//
// The best-first search loop that drives rule application lives in
// ffs_search.cpp (analog of base_optimize, substitution.cc:2229).
#pragma once

#include <algorithm>
#include <array>
#include <cstdint>
#include <functional>
#include <map>
#include <optional>
#include <set>
#include <string>
#include <vector>

#include "ffs_graph.hpp"
#include "ffs_json.hpp"

namespace ffsearch {

// Parameter constraint value: >= 0 exact; WILDCARD_BASE - v = wildcard
// variable v (bound on first use, must agree everywhere it appears).
constexpr double kWildcardBase = -1000.0;
inline double wildcard(int var) { return kWildcardBase - var; }
inline bool is_wildcard(double v) { return v <= kWildcardBase; }
inline int wildcard_var(double v) { return static_cast<int>(kWildcardBase - v); }

struct SubstOp {
  std::string type;                              // repo OperatorType name
  std::vector<std::pair<int, int>> inputs;       // (opId, tsId); opId<0 ext
  std::map<std::string, double> para;            // PM_* -> value/wildcard
};

struct SubstRule {
  std::string name;
  std::vector<SubstOp> src, dst;
  // (srcOpId, srcTsId, dstOpId, dstTsId)
  std::vector<std::array<int, 4>> mapped;
  // semantics-gated rules (e.g. Conv+BatchNorm fold uses running stats):
  // only legal when the search runs in inference mode
  bool inference_only = false;
};

// ---- loaders --------------------------------------------------------------

inline std::string map_ref_op_type(const std::string& t) {
  // substitution_loader.cc op-type vocabulary -> repo OperatorType names
  if (t == "OP_LINEAR") return "LINEAR";
  if (t == "OP_RELU") return "RELU";
  if (t == "OP_EW_ADD") return "EW_ADD";
  if (t == "OP_EW_MUL") return "EW_MUL";
  if (t == "OP_CONCAT") return "CONCAT";
  if (t == "OP_SPLIT") return "SPLIT";
  if (t == "OP_PARTITION") return "REPARTITION";
  if (t == "OP_COMBINE") return "COMBINE";
  if (t == "OP_REPLICATE") return "REPLICATE";
  if (t == "OP_REDUCE") return "REDUCTION";
  if (t.rfind("OP_", 0) == 0) return t.substr(3);  // best-effort passthrough
  return t;
}

inline SubstOp parse_subst_op(const Json& oj, bool reference_format) {
  SubstOp op;
  std::string t = oj.get("type").as_string();
  op.type = reference_format ? map_ref_op_type(t) : t;
  for (const Json& in : oj.get("input").items())
    op.inputs.push_back({(int)in.get("opId").as_int(),
                         (int)in.get("tsId").as_int(0)});
  for (const Json& p : oj.get("para").items())
    op.para[p.get("key").as_string()] = p.get("value").as_double();
  return op;
}

// Parses both the reference corpus ({"rule": [...]}, substitution_loader.cc
// RuleCollection) and this repo's native list-of-rules format.
inline std::vector<SubstRule> parse_rules(const Json& j) {
  std::vector<SubstRule> rules;
  const Json& arr = j.get("rule").is_null() ? j : j.get("rule");
  for (const Json& rj : arr.items()) {
    SubstRule r;
    r.name = rj.get("name").as_string();
    bool ref = !rj.get("_t").is_null();  // reference serializer tags types
    for (const Json& oj : rj.get("srcOp").items())
      r.src.push_back(parse_subst_op(oj, ref));
    for (const Json& oj : rj.get("dstOp").items())
      r.dst.push_back(parse_subst_op(oj, ref));
    for (const Json& mj : rj.get("mappedOutput").items())
      r.mapped.push_back({(int)mj.get("srcOpId").as_int(),
                          (int)mj.get("srcTsId").as_int(0),
                          (int)mj.get("dstOpId").as_int(),
                          (int)mj.get("dstTsId").as_int(0)});
    r.inference_only = rj.get("inference_only").as_bool(false);
    rules.push_back(std::move(r));
  }
  return rules;
}

// Hand-written generator rules (analog of substitution.cc:1726-1860) in
// wildcard form: $0 = dim, $1 = degree, $2 = activation, ...
inline std::vector<SubstRule> builtin_rules() {
  std::vector<SubstRule> rules;
  auto pm = [](std::initializer_list<std::pair<const char*, double>> kv) {
    std::map<std::string, double> m;
    for (auto& p : kv) m[p.first] = p.second;
    return m;
  };
  {
    // eliminate inverse pair: Combine(d,k) -> Repartition(d,k) => identity
    SubstRule r;
    r.name = "eliminate_combine_repartition";
    r.src = {{"COMBINE", {{-1, 0}}, pm({{"PM_PARALLEL_DIM", wildcard(0)},
                                        {"PM_PARALLEL_DEGREE", wildcard(1)}})},
             {"REPARTITION", {{0, 0}}, pm({{"PM_PARALLEL_DIM", wildcard(0)},
                                           {"PM_PARALLEL_DEGREE", wildcard(1)}})}};
    // dst: a bare pass-through boundary (Combine of degree 1 == no-op is
    // not constructible, so use a REPLICATE-free identity: re-emit the
    // repartition alone, which restores the layout the pair started from)
    r.dst = {{"REPARTITION", {{-1, 0}}, pm({{"PM_PARALLEL_DIM", wildcard(0)},
                                            {"PM_PARALLEL_DEGREE", wildcard(1)}})}};
    r.mapped = {{1, 0, 0, 0}};
    rules.push_back(std::move(r));
  }
  {
    // eliminate inverse pair: Repartition(d,k) -> Combine(d,k) => identity
    SubstRule r;
    r.name = "eliminate_repartition_combine";
    r.src = {{"REPARTITION", {{-1, 0}}, pm({{"PM_PARALLEL_DIM", wildcard(0)},
                                            {"PM_PARALLEL_DEGREE", wildcard(1)}})},
             {"COMBINE", {{0, 0}}, pm({{"PM_PARALLEL_DIM", wildcard(0)},
                                       {"PM_PARALLEL_DEGREE", wildcard(1)}})}};
    r.dst = {{"IDENTITY", {{-1, 0}}, {}}};
    r.mapped = {{1, 0, 0, 0}};
    rules.push_back(std::move(r));
  }
  {
    // move a Combine past a unary op so downstream work stays sharded:
    // Combine(d,k) -> RELU  =>  RELU -> Combine(d,k)
    for (const char* u : {"RELU", "GELU", "SIGMOID", "TANH", "IDENTITY"}) {
      SubstRule r;
      r.name = std::string("move_combine_past_") + u;
      r.src = {{"COMBINE", {{-1, 0}}, pm({{"PM_PARALLEL_DIM", wildcard(0)},
                                          {"PM_PARALLEL_DEGREE", wildcard(1)}})},
               {u, {{0, 0}}, {}}};
      r.dst = {{u, {{-1, 0}}, {}},
               {"COMBINE", {{0, 0}}, pm({{"PM_PARALLEL_DIM", wildcard(0)},
                                         {"PM_PARALLEL_DEGREE", wildcard(1)}})}};
      r.mapped = {{1, 0, 1, 0}};
      rules.push_back(std::move(r));
    }
  }
  {
    // fuse two same-input Linears into one wide Linear + Split
    // (TASO's concat-of-linears; one big MXU matmul beats two small ones)
    SubstRule r;
    r.name = "fuse_parallel_linears";
    r.src = {{"LINEAR", {{-1, 0}}, pm({{"PM_ACTI", wildcard(2)}})},
             {"LINEAR", {{-1, 0}}, pm({{"PM_ACTI", wildcard(2)}})}};
    r.dst = {{"LINEAR", {{-1, 0}}, pm({{"PM_ACTI", wildcard(2)},
                                       {"PM_MERGE", 1.0}})},
             {"SPLIT", {{0, 0}}, pm({{"PM_NUM_OUTPUTS", 2.0}})}};
    r.mapped = {{0, 0, 1, 0}, {1, 0, 1, 1}};
    rules.push_back(std::move(r));
  }
  {
    // QKV-projection merge: THREE same-input Linears -> one wide Linear
    // + 3-way Split (r4 algebraic family; generalizes
    // fuse_parallel_linears — the transformer q/k/v pattern)
    SubstRule r;
    r.name = "fuse_parallel_linears3";
    r.src = {{"LINEAR", {{-1, 0}}, pm({{"PM_ACTI", wildcard(2)}})},
             {"LINEAR", {{-1, 0}}, pm({{"PM_ACTI", wildcard(2)}})},
             {"LINEAR", {{-1, 0}}, pm({{"PM_ACTI", wildcard(2)}})}};
    r.dst = {{"LINEAR", {{-1, 0}}, pm({{"PM_ACTI", wildcard(2)},
                                       {"PM_MERGE", 1.0}})},
             {"SPLIT", {{0, 0}}, pm({{"PM_NUM_OUTPUTS", 3.0}})}};
    r.mapped = {{0, 0, 1, 0}, {1, 0, 1, 1}, {2, 0, 1, 2}};
    rules.push_back(std::move(r));
  }
  {
    // activation-epilogue fusion: LINEAR(none) -> act  =>  LINEAR(act).
    // On TPU the activation runs in the matmul's epilogue fusion — the
    // standalone op's dispatch + HBM round-trip disappears (r4 family).
    struct ActKind { const char* op; double acti; };
    for (ActKind a : {ActKind{"RELU", 1.0}, ActKind{"SIGMOID", 2.0},
                      ActKind{"TANH", 3.0}, ActKind{"GELU", 4.0}}) {
      SubstRule r;
      r.name = std::string("fuse_linear_") + a.op;
      r.src = {{"LINEAR", {{-1, 0}}, pm({{"PM_ACTI", 0.0}})},
               {a.op, {{0, 0}}, {}}};
      r.dst = {{"LINEAR", {{-1, 0}}, pm({{"PM_ACTI", a.acti}})}};
      r.mapped = {{1, 0, 0, 0}};
      rules.push_back(std::move(r));
    }
  }
  {
    // fuse_parallel_ops (reference substitution.cc:1925): adjacent
    // parallel-op chains collapse into ONE FusedParallelOp boundary — a
    // single reshard instead of two sequential collectives.
    for (int d1 = 0; d1 < 3; ++d1) {
      for (int d2 = 0; d2 < 3; ++d2) {
        if (d1 == d2) continue;
        // Repartition(d1) -> Combine(d2): move shards between dims
        SubstRule r;
        r.name = "fuse_parallel_ops_part" + std::to_string(d1) + "_comb" +
                 std::to_string(d2);
        r.src = {{"REPARTITION", {{-1, 0}},
                  pm({{"PM_PARALLEL_DIM", (double)d1},
                      {"PM_PARALLEL_DEGREE", wildcard(1)}})},
                 {"COMBINE", {{0, 0}},
                  pm({{"PM_PARALLEL_DIM", (double)d2},
                      {"PM_PARALLEL_DEGREE", wildcard(3)}})}};
        r.dst = {{"FUSED_PARALLEL", {{-1, 0}}, {}}};
        r.mapped = {{1, 0, 0, 0}};
        rules.push_back(std::move(r));
      }
    }
    // Combine(d) -> Replicate: gather + broadcast in one boundary
    for (int d = 0; d < 3; ++d) {
      SubstRule r;
      r.name = "fuse_parallel_ops_comb" + std::to_string(d) + "_repl";
      r.src = {{"COMBINE", {{-1, 0}},
                pm({{"PM_PARALLEL_DIM", (double)d},
                    {"PM_PARALLEL_DEGREE", wildcard(1)}})},
               {"REPLICATE", {{0, 0}}, {}}};
      r.dst = {{"FUSED_PARALLEL", {{-1, 0}}, {}}};
      r.mapped = {{1, 0, 0, 0}};
      rules.push_back(std::move(r));
    }
  }
  {
    // move Combines past a binary op: Combine(a)+Combine(b) -> EW op
    // => EW op -> Combine — one all-gather instead of two, and the
    // elementwise work stays sharded (reference's partition rules around
    // element-wise chains, substitution.cc:1726)
    for (const char* b : {"EW_ADD", "EW_MUL"}) {
      SubstRule r;
      r.name = std::string("move_combines_past_") + b;
      r.src = {{"COMBINE", {{-1, 0}}, pm({{"PM_PARALLEL_DIM", wildcard(0)},
                                          {"PM_PARALLEL_DEGREE", wildcard(1)}})},
               {"COMBINE", {{-2, 0}}, pm({{"PM_PARALLEL_DIM", wildcard(0)},
                                          {"PM_PARALLEL_DEGREE", wildcard(1)}})},
               {b, {{0, 0}, {1, 0}}, {}}};
      r.dst = {{b, {{-1, 0}, {-2, 0}}, {}},
               {"COMBINE", {{0, 0}}, pm({{"PM_PARALLEL_DIM", wildcard(0)},
                                         {"PM_PARALLEL_DEGREE", wildcard(1)}})}};
      r.mapped = {{2, 0, 1, 0}};
      rules.push_back(std::move(r));
    }
  }
  {
    // move a batch-dim Combine past shape-preserving grid ops so the conv
    // work stays sharded (create_partition_conv2d_combine analog,
    // substitution.cc:1744): Combine(0,k) -> Conv/Pool/BN
    // => Conv/Pool/BN -> Combine(0,k)
    // BATCHNORM note: under GSPMD a Combine/Repartition is a layout
    // constraint, not data movement — BatchNorm's jnp.mean over the batch
    // dim always computes GLOBAL-batch statistics (XLA inserts the
    // cross-shard reduction when the dim is sharded), so this rewrite is
    // numerics-preserving here, unlike a runtime that would compute
    // per-shard local stats (advisor r3 finding: convention documented).
    for (const char* g : {"CONV2D", "POOL2D", "BATCHNORM", "LAYERNORM"}) {
      SubstRule r;
      r.name = std::string("move_combine_past_") + g;
      r.src = {{"COMBINE", {{-1, 0}}, pm({{"PM_PARALLEL_DIM", 0.0},
                                          {"PM_PARALLEL_DEGREE", wildcard(1)}})},
               {g, {{0, 0}}, {}}};
      r.dst = {{g, {{-1, 0}}, {}},
               {"COMBINE", {{0, 0}}, pm({{"PM_PARALLEL_DIM", 0.0},
                                         {"PM_PARALLEL_DEGREE", wildcard(1)}})}};
      r.mapped = {{1, 0, 1, 0}};
      rules.push_back(std::move(r));
    }
  }
  {
    // push a Repartition above a unary op: RELU -> Repartition(d,k)
    // => Repartition(d,k) -> RELU — the elementwise work runs sharded
    // (the reference's create_partition_relu_combine, substitution.cc:1726)
    for (const char* u : {"RELU", "GELU", "SIGMOID", "TANH"}) {
      SubstRule r;
      r.name = std::string("move_repartition_before_") + u;
      r.src = {{u, {{-1, 0}}, {}},
               {"REPARTITION", {{0, 0}}, pm({{"PM_PARALLEL_DIM", wildcard(0)},
                                             {"PM_PARALLEL_DEGREE", wildcard(1)}})}};
      r.dst = {{"REPARTITION", {{-1, 0}}, pm({{"PM_PARALLEL_DIM", wildcard(0)},
                                              {"PM_PARALLEL_DEGREE", wildcard(1)}})},
               {u, {{0, 0}}, {}}};
      r.mapped = {{1, 0, 1, 0}};
      rules.push_back(std::move(r));
    }
  }
  {
    // Concat of two same-degree Combines => Concat -> one Combine, when
    // the concat axis differs from the combine dim (same-dim case would
    // interleave shard groups — unsafe). (create_partition_concat_combine
    // analog, substitution.cc:1793.)
    for (int d = 0; d < 3; ++d) {
      for (int a = 0; a < 3; ++a) {
        if (a == d) continue;
        SubstRule r;
        r.name = "concat_of_combines_d" + std::to_string(d) + "_a" +
                 std::to_string(a);
        r.src = {{"COMBINE", {{-1, 0}}, pm({{"PM_PARALLEL_DIM", (double)d},
                                            {"PM_PARALLEL_DEGREE", wildcard(1)}})},
                 {"COMBINE", {{-2, 0}}, pm({{"PM_PARALLEL_DIM", (double)d},
                                            {"PM_PARALLEL_DEGREE", wildcard(1)}})},
                 {"CONCAT", {{0, 0}, {1, 0}}, pm({{"PM_AXIS", (double)a}})}};
        r.dst = {{"CONCAT", {{-1, 0}, {-2, 0}}, pm({{"PM_AXIS", (double)a}})},
                 {"COMBINE", {{0, 0}}, pm({{"PM_PARALLEL_DIM", (double)d},
                                           {"PM_PARALLEL_DEGREE", wildcard(1)}})}};
        r.mapped = {{2, 0, 1, 0}};
        rules.push_back(std::move(r));
      }
    }
  }
  return rules;
}

// ---- matching -------------------------------------------------------------

struct Match {
  std::vector<int> node_of;                       // pattern op -> node index
  std::map<int, std::pair<int64_t, int>> ext;     // ext id -> (guid, ts)
  std::map<int, double> vars;                     // wildcard bindings
};

namespace subst_detail {

// Graph-side value of a PM constraint key on a node.
inline std::optional<double> node_param(const Node& n, const std::string& key) {
  if (key == "PM_PARALLEL_DIM") {
    const Json& v = n.attrs.get("dim");
    if (!v.is_null()) return v.as_double();
    return std::nullopt;
  }
  if (key == "PM_PARALLEL_DEGREE") {
    const Json& v = n.attrs.get("degree");
    if (!v.is_null()) return v.as_double();
    return std::nullopt;
  }
  if (key == "PM_ACTI") {
    const Json& v = n.attrs.get("activation");
    if (!v.is_null()) return v.as_double();
    return 0.0;  // AC_MODE_NONE
  }
  if (key == "PM_AXIS") {
    const Json& v = n.attrs.get("axis");
    if (!v.is_null()) return v.as_double();
    return std::nullopt;
  }
  if (key == "PM_RELU") {
    const Json& v = n.attrs.get("relu");
    if (!v.is_null()) return v.as_double();
    return 0.0;
  }
  if (key == "PM_NUM_INPUTS") return (double)n.inputs.size();
  if (key == "PM_NUM_OUTPUTS") return (double)n.output_shapes.size();
  if (key == "PM_NUMDIM")
    return n.output_shapes.empty() ? 0.0 : (double)n.output_shapes[0].size();
  return std::nullopt;  // unknown key: cannot verify -> no match
}

inline bool check_params(const SubstOp& pop, const Node& n, Match& m) {
  for (const auto& kv : pop.para) {
    auto got = node_param(n, kv.first);
    if (!got) return false;
    if (is_wildcard(kv.second)) {
      int var = wildcard_var(kv.second);
      auto it = m.vars.find(var);
      if (it == m.vars.end())
        m.vars[var] = *got;
      else if (it->second != *got)
        return false;
    } else if (*got != kv.second) {
      return false;
    }
  }
  return true;
}

}  // namespace subst_detail

// All matches of `rule.src` in `g`. A matched internal tensor may not have
// consumers outside the match unless it is a mapped output (the reference's
// "no external uses of intermediates" check in GraphXfer::match).
inline std::vector<Match> find_matches(const Graph& g, const SubstRule& rule,
                                       size_t limit = 16) {
  std::vector<Match> out;
  const size_t P = rule.src.size();
  if (P == 0) return out;
  Match m;
  m.node_of.assign(P, -1);
  std::vector<bool> used(g.nodes.size(), false);

  // which (srcOp, ts) pairs escape via mappedOutput
  std::set<std::pair<int, int>> mapped_src;
  for (const auto& mo : rule.mapped) mapped_src.insert({mo[0], mo[1]});

  std::function<bool(size_t)> try_op = [&](size_t pi) -> bool {
    if (out.size() >= limit) return true;
    if (pi == P) {
      // verify intermediates have no external consumers
      std::set<int> in_match(m.node_of.begin(), m.node_of.end());
      for (size_t i = 0; i < P; ++i) {
        const Node& n = g.nodes[m.node_of[i]];
        for (size_t ts = 0; ts < n.output_shapes.size(); ++ts) {
          if (mapped_src.count({(int)i, (int)ts})) continue;
          auto it = g.consumers.find(n.guid);
          if (it == g.consumers.end()) continue;
          for (const auto& c : it->second) {
            // consumer must be inside the match and reference this ts
            const Node& cn = g.nodes[c.first];
            const EdgeRef& e = cn.inputs[c.second];
            if (e.src_idx == (int)ts && !in_match.count(c.first)) return false;
          }
        }
      }
      out.push_back(m);
      return out.size() >= limit;
    }
    const SubstOp& pop = rule.src[pi];
    for (size_t ni = 0; ni < g.nodes.size(); ++ni) {
      if (used[ni]) continue;
      const Node& n = g.nodes[ni];
      if (n.type != pop.type) continue;
      if (n.inputs.size() != pop.inputs.size()) continue;
      // an op the graph says no rewrite may re-form: one whose leaves
      // other ops read or that reads another's (the rewritten op would
      // hold leaves of its own), a product stated in full precision
      if (n.attrs.get("pinned").as_double(0.0) > 0) continue;
      Match saved = m;
      bool ok = subst_detail::check_params(pop, n, m);
      // edge consistency
      for (size_t slot = 0; ok && slot < pop.inputs.size(); ++slot) {
        auto [src_op, src_ts] = pop.inputs[slot];
        const EdgeRef& e = n.inputs[slot];
        if (src_op >= 0) {
          // must come from already-matched pattern op (patterns are listed
          // in topological order in both formats)
          int mn = m.node_of[src_op];
          if (mn < 0 || e.src_guid != g.nodes[mn].guid || e.src_idx != src_ts)
            ok = false;
        } else {
          auto key = src_op * 1000 + src_ts;  // unique ext id
          auto it = m.ext.find(key);
          std::pair<int64_t, int> ref{e.src_guid, e.src_idx};
          if (it == m.ext.end())
            m.ext[key] = ref;
          else if (it->second != ref)
            ok = false;
        }
      }
      if (ok) {
        m.node_of[pi] = static_cast<int>(ni);
        used[ni] = true;
        if (try_op(pi + 1)) return true;
        used[ni] = false;
      }
      m = std::move(saved);
      m.node_of[pi] = -1;
    }
    return false;
  };
  try_op(0);
  return out;
}

// ---- application ----------------------------------------------------------

struct RewriteTraceEntry {
  std::string rule;
  std::vector<int64_t> removed;  // guids of removed nodes
  Json added = Json::array();    // node descriptors Python can rebuild
  // (old_guid, old_ts, new_guid, new_ts) for rule-mapped outputs, so the
  // caller can chase the model's final output through rewrites
  std::vector<std::array<int64_t, 4>> output_remap;
};

namespace subst_detail {

inline Json shape_json(const Shape& s) {
  Json a = Json::array();
  for (int64_t d : s) a.push_back(Json(d));
  return a;
}

}  // namespace subst_detail

// Apply `rule` at `match`. Returns the rewritten graph or nullopt when the
// replacement cannot be constructed (shape mismatch / non-inferable op).
inline std::optional<Graph> apply_rule(const Graph& g, const SubstRule& rule,
                                       const Match& match, int64_t* next_guid,
                                       RewriteTraceEntry* trace) {
  // resolve a pattern-side tensor ref to a (guid, ts) in the new graph
  std::set<int> removed_idx(match.node_of.begin(), match.node_of.end());

  // dst op j of type T inherits attrs/params from the j-th src op of type T
  std::map<std::string, std::vector<int>> src_of_type;
  for (size_t i = 0; i < rule.src.size(); ++i)
    src_of_type[rule.src[i].type].push_back(match.node_of[i]);
  std::map<std::string, size_t> taken;

  std::vector<Node> new_nodes;
  std::vector<std::pair<int64_t, int>> dst_out_ref(rule.dst.size() * 4,
                                                   {-1, 0});
  auto dst_ref = [&](int op, int ts) { return dst_out_ref[op * 4 + ts]; };

  // sentinel for "dst uses an external the src pattern never bound" — must
  // not collide with real graph-input ids (small negative guids)
  constexpr int64_t kUnbound = INT64_MIN;
  auto ext_ref = [&](int op_id, int ts_id) -> std::pair<int64_t, int> {
    auto it = match.ext.find(op_id * 1000 + ts_id);
    if (it != match.ext.end()) return it->second;
    return {kUnbound, 0};
  };

  auto para_val = [&](const SubstOp& op, const char* key,
                      double dflt) -> double {
    auto it = op.para.find(key);
    if (it == op.para.end()) return dflt;
    if (is_wildcard(it->second)) {
      auto vit = match.vars.find(wildcard_var(it->second));
      return vit == match.vars.end() ? dflt : vit->second;
    }
    return it->second;
  };

  // shape of a tensor ref (graph node / new node / graph input)
  auto shape_of = [&](std::pair<int64_t, int> ref) -> std::optional<Shape> {
    if (ref.first < 0) {
      // graph input: find a node consuming this exact external id
      for (const Node& n : g.nodes)
        for (size_t s = 0; s < n.inputs.size(); ++s)
          if (n.inputs[s].src_guid == ref.first &&
              s < n.input_shapes.size())
            return n.input_shapes[s];
      return std::nullopt;
    }
    auto it = g.index_of.find(ref.first);
    if (it != g.index_of.end())
      return g.nodes[it->second].output_shapes[ref.second];
    for (const Node& n : new_nodes)
      if (n.guid == ref.first) return n.output_shapes[ref.second];
    return std::nullopt;
  };

  for (size_t di = 0; di < rule.dst.size(); ++di) {
    const SubstOp& dop = rule.dst[di];
    Node n;
    n.guid = (*next_guid)++;
    n.type = dop.type;
    n.name = rule.name + "_" + std::to_string(n.guid);
    // inherit from positional same-type src op when available
    int inherit = -1;
    auto& avail = src_of_type[dop.type];
    size_t& k = taken[dop.type];
    if (k < avail.size()) inherit = avail[k++];
    const Node* base = inherit >= 0 ? &g.nodes[inherit] : nullptr;
    if (base) {
      n.attrs = base->attrs;
      n.params = base->params;
      n.dtype_size = base->dtype_size;
      n.act_dtype_size = base->act_dtype_size;
      n.fwd_flops = base->fwd_flops;
    } else {
      n.dtype_size = g.nodes[match.node_of[0]].dtype_size;
      n.act_dtype_size = g.nodes[match.node_of[0]].act_dtype_size;
    }

    // wire inputs + collect input shapes
    std::vector<Shape> in_shapes;
    for (auto [op_id, ts_id] : dop.inputs) {
      std::pair<int64_t, int> ref =
          op_id >= 0 ? dst_ref(op_id, ts_id) : ext_ref(op_id, ts_id);
      if (ref.first == kUnbound) return std::nullopt;
      n.inputs.push_back({ref.first, ref.second});
      auto shp = shape_of(ref);
      if (!shp) return std::nullopt;
      in_shapes.push_back(*shp);
    }
    n.input_shapes = in_shapes;

    // local shape/attr inference per type
    const std::string& t = n.type;
    if (t == "REPARTITION" || t == "COMBINE" || t == "REPLICATE") {
      if (in_shapes.size() != 1) return std::nullopt;
      Json attrs = Json::object();
      attrs.set("dim", Json((int64_t)para_val(dop, "PM_PARALLEL_DIM", 0)));
      attrs.set("degree", Json((int64_t)para_val(dop, "PM_PARALLEL_DEGREE", 1)));
      n.attrs = attrs;
      n.output_shapes = {in_shapes[0]};
      int64_t dim = (int64_t)para_val(dop, "PM_PARALLEL_DIM", 0);
      int64_t deg = (int64_t)para_val(dop, "PM_PARALLEL_DEGREE", 1);
      if (t != "REPLICATE" &&
          (dim < 0 || dim >= (int64_t)in_shapes[0].size() ||
           deg <= 0 || in_shapes[0][dim] % deg))
        return std::nullopt;
      n.fwd_flops = 0;
    } else if (t == "REDUCTION") {
      // explicit-shape form: reduces groups along the dim — reference
      // replica-dim rules won't shape-check and are skipped here
      if (in_shapes.size() != 1) return std::nullopt;
      int64_t dim = (int64_t)para_val(dop, "PM_PARALLEL_DIM", 0);
      int64_t deg = (int64_t)para_val(dop, "PM_PARALLEL_DEGREE", 1);
      if (dim < 0 || dim >= (int64_t)in_shapes[0].size() || deg <= 0 ||
          in_shapes[0][dim] % deg)
        return std::nullopt;
      Shape s = in_shapes[0];
      s[dim] /= deg;
      Json attrs = Json::object();
      attrs.set("dim", Json(dim));
      attrs.set("degree", Json(deg));
      n.attrs = attrs;
      n.output_shapes = {s};
      n.fwd_flops = (double)shape_elems(in_shapes[0]);
    } else if (t == "IDENTITY" || t == "RELU" || t == "GELU" ||
               t == "SIGMOID" || t == "TANH" || t == "ELU" || t == "EXP" ||
               t == "SIN" || t == "COS" || t == "RSQRT" || t == "DROPOUT" ||
               t == "CAST" || t.rfind("SCALAR_", 0) == 0) {
      if (in_shapes.size() != 1) return std::nullopt;
      n.output_shapes = {in_shapes[0]};
      n.fwd_flops = (double)shape_elems(in_shapes[0]);
      n.params.clear();
    } else if (t == "CONV2D" || t == "POOL2D" || t == "BATCHNORM" ||
               t == "LAYERNORM") {
      // shape-preserving re-emission: the dst op must inherit from a
      // matched src op of the same type with identical input shape (rules
      // only move layout boundaries around these; nothing is resized)
      if (base == nullptr || in_shapes.empty() ||
          base->input_shapes.empty() || in_shapes[0] != base->input_shapes[0])
        return std::nullopt;
      n.output_shapes = base->output_shapes;
      n.fwd_flops = base->fwd_flops;
      n.params = base->params;
      // BN-fold overrides: the folded conv gains a bias and possibly the
      // BN's fused relu
      double acti = para_val(dop, "PM_ACTI", -1.0);
      double ub = para_val(dop, "PM_USE_BIAS", -1.0);
      if (acti >= 0 || ub >= 0) {
        Json attrs = n.attrs;
        if (acti >= 0) attrs.set("activation", Json(acti));
        if (ub >= 0) attrs.set("use_bias", Json((int64_t)ub));
        n.attrs = attrs;
        if (ub > 0 && !n.params.count("bias") && !n.output_shapes.empty() &&
            n.output_shapes[0].size() == 4)
          n.params["bias"] = {n.output_shapes[0][1]};  // NCHW channels
      }
    } else if (t == "FUSED_PARALLEL") {
      // fuse_parallel_ops: collapse the matched parallel-op chain into
      // one boundary. Steps come from the matched src ops in pattern
      // order; only non-REDUCTION steps are generated (shape-preserving).
      if (in_shapes.size() != 1) return std::nullopt;
      Json steps = Json::array();
      for (size_t si = 0; si < rule.src.size(); ++si) {
        const std::string& st_ = rule.src[si].type;
        if (st_ != "REPARTITION" && st_ != "COMBINE" && st_ != "REPLICATE")
          continue;
        const Node& sn = g.nodes[match.node_of[si]];
        int64_t dim = sn.attrs.get("dim").as_int(0);
        int64_t deg = sn.attrs.get("degree").as_int(1);
        Json step = Json::array();
        step.push_back(Json(st_));
        step.push_back(Json(dim));
        step.push_back(Json(deg));
        if (st_ == "REPARTITION" &&
            (dim < 0 || dim >= (int64_t)in_shapes[0].size() || deg <= 0 ||
             in_shapes[0][dim] % deg))
          return std::nullopt;
        steps.push_back(step);
      }
      if (steps.items().empty()) return std::nullopt;
      Json attrs = Json::object();
      attrs.set("ops", steps);
      n.attrs = attrs;
      n.output_shapes = {in_shapes[0]};
      n.fwd_flops = 0;
      n.params.clear();
    } else if (t.rfind("EW_", 0) == 0) {
      if (in_shapes.size() != 2) return std::nullopt;
      const Shape &a = in_shapes[0], &b = in_shapes[1];
      // Soundness: rules that move parallel ops across a binary assume
      // dim index i means the same logical axis in BOTH operands; under
      // rank-mismatched broadcast (e.g. bias [D] against [B,S,D]) dim 0
      // of the low-rank operand is a different axis and the rewrite
      // would shard operands inconsistently. Equal rank restores the
      // correspondence; size-1 broadcast dims stay safe because the
      // parallel-op emission's divisibility check (1 % deg) rejects
      // sharding them.
      if (a.size() != b.size()) return std::nullopt;
      size_t rank = std::max(a.size(), b.size());
      Shape o(rank, 1);
      for (size_t i = 0; i < rank; ++i) {
        int64_t da = i < rank - a.size() ? 1 : a[i - (rank - a.size())];
        int64_t db = i < rank - b.size() ? 1 : b[i - (rank - b.size())];
        if (da != db && da != 1 && db != 1) return std::nullopt;
        o[i] = std::max(da, db);
      }
      n.output_shapes = {o};
      n.fwd_flops = (double)shape_elems(o);
      n.params.clear();
    } else if (t == "LINEAR") {
      if (in_shapes.size() != 1 || in_shapes[0].empty()) return std::nullopt;
      int64_t in_dim = in_shapes[0].back();
      int64_t out_dim;
      if (para_val(dop, "PM_MERGE", 0.0) > 0) {
        // wide fusion: out = sum of all matched src linears' out dims
        out_dim = 0;
        for (int si : src_of_type["LINEAR"]) {
          const Node& sn = g.nodes[si];
          auto kit = sn.params.find("kernel");
          if (kit == sn.params.end() || kit->second.size() != 2 ||
              kit->second[0] != in_dim)
            return std::nullopt;
          out_dim += kit->second[1];
        }
      } else if (base) {
        auto kit = base->params.find("kernel");
        if (kit == base->params.end() || kit->second.size() != 2 ||
            kit->second[0] != in_dim)
          return std::nullopt;
        out_dim = kit->second[1];
      } else {
        return std::nullopt;  // no source to infer the weight from
      }
      Shape o = in_shapes[0];
      o.back() = out_dim;
      n.output_shapes = {o};
      n.params.clear();
      n.params["kernel"] = {in_dim, out_dim};
      n.params["bias"] = {out_dim};
      int64_t rows = 1;
      for (size_t i = 0; i + 1 < in_shapes[0].size(); ++i)
        rows *= in_shapes[0][i];
      n.fwd_flops = 2.0 * rows * in_dim * out_dim;
      Json attrs = base ? base->attrs : Json::object();
      attrs.set("out_dim", Json(out_dim));
      double acti = para_val(dop, "PM_ACTI", -1.0);
      if (acti >= 0) attrs.set("activation", Json(acti));
      n.attrs = attrs;
    } else if (t == "CONCAT") {
      if (in_shapes.empty()) return std::nullopt;
      int64_t axis = (int64_t)para_val(dop, "PM_AXIS", 0);
      if (axis < 0 || axis >= (int64_t)in_shapes[0].size()) return std::nullopt;
      Shape o = in_shapes[0];
      o[axis] = 0;
      for (const Shape& s : in_shapes) {
        if (s.size() != o.size()) return std::nullopt;
        for (size_t i = 0; i < s.size(); ++i)
          if ((int64_t)i != axis && s[i] != o[i]) return std::nullopt;
        o[axis] += s[axis];
      }
      Json attrs = Json::object();
      attrs.set("axis", Json(axis));
      n.attrs = attrs;
      n.output_shapes = {o};
      n.fwd_flops = 0;
      n.params.clear();
    } else if (t == "SPLIT") {
      if (in_shapes.size() != 1) return std::nullopt;
      // split the last dim back into the matched linears' out widths when
      // this is the fusion rule's tail; otherwise equal split via
      // PM_NUM_OUTPUTS on PM_AXIS
      int64_t axis = (int64_t)para_val(
          dop, "PM_AXIS", (double)(in_shapes[0].size() - 1));
      int64_t nout = (int64_t)para_val(dop, "PM_NUM_OUTPUTS", 2);
      if (axis < 0 || axis >= (int64_t)in_shapes[0].size() || nout <= 0)
        return std::nullopt;
      std::vector<int64_t> sizes;
      auto& lins = src_of_type["LINEAR"];
      if ((int64_t)lins.size() == nout) {
        for (int si : lins) {
          auto kit = g.nodes[si].params.find("kernel");
          if (kit == g.nodes[si].params.end()) return std::nullopt;
          sizes.push_back(kit->second[1]);
        }
      } else {
        if (in_shapes[0][axis] % nout) return std::nullopt;
        sizes.assign(nout, in_shapes[0][axis] / nout);
      }
      int64_t total = 0;
      for (int64_t s : sizes) total += s;
      if (total != in_shapes[0][axis]) return std::nullopt;
      for (int64_t sz : sizes) {
        Shape o = in_shapes[0];
        o[axis] = sz;
        n.output_shapes.push_back(o);
      }
      Json attrs = Json::object();
      attrs.set("axis", Json(axis));
      Json szs = Json::array();
      for (int64_t s : sizes) szs.push_back(Json(s));
      attrs.set("sizes", szs);
      n.attrs = attrs;
      n.fwd_flops = 0;
      n.params.clear();
    } else {
      return std::nullopt;  // unsupported dst op type
    }

    // roles: copy from inherited src, else sample+other
    if (base && !base->roles.empty() &&
        base->output_shapes.size() == n.output_shapes.size()) {
      n.roles = base->roles;
    } else {
      n.roles.clear();
      for (const Shape& s : n.output_shapes) {
        std::vector<Role> rr(s.size(), Role::Other);
        if (!rr.empty()) rr[0] = Role::Sample;
        n.roles.push_back(rr);
      }
    }

    for (size_t ts = 0; ts < n.output_shapes.size() && ts < 4; ++ts)
      dst_out_ref[di * 4 + ts] = {n.guid, (int)ts};
    new_nodes.push_back(std::move(n));
  }

  // output remap: (src guid, ts) -> (dst guid, ts)
  std::map<std::pair<int64_t, int>, std::pair<int64_t, int>> remap;
  for (const auto& mo : rule.mapped) {
    int64_t sg = g.nodes[match.node_of[mo[0]]].guid;
    remap[{sg, mo[1]}] = dst_ref(mo[2], mo[3]);
  }

  // splice: keep unmatched nodes, rewiring consumers of mapped outputs;
  // insert new nodes right where the first matched node stood (keeps
  // topological order because dst inputs are externals or earlier dst ops)
  Graph out;
  size_t insert_at = g.nodes.size();
  for (size_t i = 0; i < g.nodes.size(); ++i)
    if (removed_idx.count((int)i)) { insert_at = i; break; }

  std::set<std::pair<int64_t, int>> unmapped_removed;
  for (int ni : match.node_of) {
    const Node& n = g.nodes[ni];
    for (size_t ts = 0; ts < n.output_shapes.size(); ++ts)
      if (!remap.count({n.guid, (int)ts}))
        unmapped_removed.insert({n.guid, (int)ts});
  }

  for (size_t i = 0; i < g.nodes.size(); ++i) {
    if (i == insert_at)
      for (Node& nn : new_nodes) out.nodes.push_back(nn);
    if (removed_idx.count((int)i)) continue;
    Node n = g.nodes[i];
    for (EdgeRef& e : n.inputs) {
      auto it = remap.find({e.src_guid, e.src_idx});
      if (it != remap.end()) {
        e.src_guid = it->second.first;
        e.src_idx = it->second.second;
      } else if (unmapped_removed.count({e.src_guid, e.src_idx})) {
        return std::nullopt;  // consumer of an output the rule dropped
      }
    }
    out.nodes.push_back(std::move(n));
  }
  if (insert_at == g.nodes.size())
    for (Node& nn : new_nodes) out.nodes.push_back(nn);

  for (size_t i = 0; i < out.nodes.size(); ++i)
    out.index_of[out.nodes[i].guid] = static_cast<int>(i);
  for (size_t i = 0; i < out.nodes.size(); ++i)
    for (size_t slot = 0; slot < out.nodes[i].inputs.size(); ++slot) {
      const EdgeRef& r = out.nodes[i].inputs[slot];
      if (r.src_guid >= 0) {
        if (!out.index_of.count(r.src_guid)) return std::nullopt;
        out.consumers[r.src_guid].push_back({(int)i, (int)slot});
      }
    }

  if (trace) {
    trace->rule = rule.name;
    for (int ni : match.node_of) trace->removed.push_back(g.nodes[ni].guid);
    for (const auto& kv : remap)
      trace->output_remap.push_back({kv.first.first, (int64_t)kv.first.second,
                                     kv.second.first,
                                     (int64_t)kv.second.second});
    for (const Node& nn : new_nodes) {
      Json nd = Json::object();
      nd.set("guid", Json(nn.guid));
      nd.set("type", Json(nn.type));
      nd.set("name", Json(nn.name));
      Json ins = Json::array();
      for (const EdgeRef& e : nn.inputs) {
        Json pair = Json::array();
        pair.push_back(Json((int64_t)e.src_guid));
        pair.push_back(Json((int64_t)e.src_idx));
        ins.push_back(pair);
      }
      nd.set("inputs", ins);
      nd.set("attrs", nn.attrs);
      Json oshp = Json::array();
      for (const Shape& s : nn.output_shapes)
        oshp.push_back(subst_detail::shape_json(s));
      nd.set("output_shapes", oshp);
      trace->added.push_back(nd);
    }
  }
  return out;
}

// Structural hash for the seen-set of the best-first loop.
inline std::string graph_key(const Graph& g) {
  std::string k;
  for (const Node& n : g.nodes) {
    k += n.type;
    k += ':';
    for (const EdgeRef& e : n.inputs) {
      k += std::to_string(e.src_guid);
      k += '.';
      k += std::to_string(e.src_idx);
      k += ',';
    }
    for (const Shape& s : n.output_shapes)
      for (int64_t d : s) {
        k += std::to_string(d);
        k += 'x';
      }
    k += n.attrs.dump();
    k += ';';
  }
  return k;
}

}  // namespace ffsearch
