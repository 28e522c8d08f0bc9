// PCG graph representation for the search core.
//
// Analog of PCG::Graph (include/flexflow/graph.h:293): nodes are compute
// ops with global (unsharded) shapes; parallelization is a per-node
// *sharding choice* (see ffs_strategy.hpp) rather than inserted parallel
// ops — under GSPMD the four resharding operators become spec transitions
// on edges, so the search manipulates specs directly and the Python side
// materializes constraint boundaries from them.
#pragma once

#include <algorithm>
#include <cstdint>
#include <map>
#include <string>
#include <vector>

#include "ffs_json.hpp"

namespace ffsearch {

using Shape = std::vector<int64_t>;

inline int64_t shape_elems(const Shape& s) {
  int64_t n = 1;
  for (int64_t d : s) n *= d;
  return n;
}

enum class Role : uint8_t { Sample, Channel, Head, Seq, Expert, Other };

inline Role role_from_string(const std::string& s) {
  if (s == "sample") return Role::Sample;
  if (s == "channel") return Role::Channel;
  if (s == "head") return Role::Head;
  if (s == "seq") return Role::Seq;
  if (s == "expert") return Role::Expert;
  return Role::Other;
}

struct EdgeRef {
  int64_t src_guid = -1;  // -1 => graph input (fed from host)
  int src_idx = 0;
};

struct Node {
  int64_t guid = 0;
  std::string type;  // OperatorType name, e.g. "LINEAR"
  std::string name;
  std::vector<EdgeRef> inputs;
  std::vector<Shape> input_shapes;
  std::vector<Shape> output_shapes;
  std::vector<std::vector<Role>> roles;        // per output dim roles
  std::map<std::string, Shape> params;          // param name -> shape
  double fwd_flops = 0.0;
  int dtype_size = 4;
  // element size of what the op's forward leaves for its backward pass:
  // the compute dtype's under mixed precision (bfloat16 activations
  // beside float32 leaves), else `dtype_size`
  int act_dtype_size = 4;
  Json attrs;  // op-specific attributes (num_heads, axis, ...)

  int64_t param_bytes() const {
    int64_t b = 0;
    for (const auto& kv : params) b += shape_elems(kv.second) * dtype_size;
    return b;
  }
  int64_t output_bytes(int i) const {
    return shape_elems(output_shapes[i]) * dtype_size;
  }
  // a saved output's bytes, and the op's stated interior at the same
  // element size (the memory terms; times are priced on `output_bytes`)
  double act_bytes(int i) const {
    return (double)shape_elems(output_shapes[i]) * act_dtype_size;
  }
  double act_interior_bytes() const {
    return attrs.get("interior_bytes").as_double(0.0) * act_dtype_size /
           dtype_size;
  }
  int64_t input_bytes(int i) const {
    return shape_elems(input_shapes[i]) * dtype_size;
  }
  // outputs that occupy and move bytes: all but the last
  // `aliased_outputs`, which are inputs handed through (a hyper-
  // connection's stream: the reader of THAT output hands its cotangent
  // to the producer's backward)
  size_t own_outputs() const {
    size_t aliased = (size_t)attrs.get("aliased_outputs").as_double(0.0);
    return output_shapes.size() - std::min(aliased, output_shapes.size());
  }
  int64_t total_io_bytes() const {
    int64_t b = param_bytes();
    for (size_t i = 0; i < input_shapes.size(); ++i) b += input_bytes(i);
    for (size_t i = 0; i < own_outputs(); ++i) b += output_bytes(i);
    // intermediates an op with a wide interior writes between its own
    // stages (the op states them; 0 for every other op)
    b += static_cast<int64_t>(attrs.get("interior_bytes").as_double(0.0));
    return b;
  }
};

struct Graph {
  std::vector<Node> nodes;            // topological order (as built)
  std::map<int64_t, int> index_of;    // guid -> index in nodes
  // consumers[guid] = list of (consumer node index, consumer input slot)
  std::map<int64_t, std::vector<std::pair<int, int>>> consumers;

  static Graph from_json(const Json& j) {
    Graph g;
    for (const Json& nj : j.items()) {
      Node n;
      n.guid = nj.get("guid").as_int();
      n.type = nj.get("type").as_string();
      n.name = nj.get("name").as_string();
      for (const Json& e : nj.get("inputs").items()) {
        EdgeRef r;
        r.src_guid = e[0].as_int(-1);
        r.src_idx = static_cast<int>(e[1].as_int(0));
        n.inputs.push_back(r);
      }
      auto parse_shapes = [](const Json& arr) {
        std::vector<Shape> out;
        for (const Json& sj : arr.items()) {
          Shape s;
          for (const Json& d : sj.items()) s.push_back(d.as_int());
          out.push_back(s);
        }
        return out;
      };
      n.input_shapes = parse_shapes(nj.get("input_shapes"));
      n.output_shapes = parse_shapes(nj.get("output_shapes"));
      for (const Json& rj : nj.get("roles").items()) {
        std::vector<Role> rr;
        for (const Json& r : rj.items()) rr.push_back(role_from_string(r.as_string()));
        n.roles.push_back(rr);
      }
      for (const auto& kv : nj.get("params").fields()) {
        Shape s;
        for (const Json& d : kv.second.items()) s.push_back(d.as_int());
        n.params[kv.first] = s;
      }
      n.fwd_flops = nj.get("flops").as_double();
      n.dtype_size = static_cast<int>(nj.get("dtype_size").as_int(4));
      n.act_dtype_size = static_cast<int>(
          nj.get("act_dtype_size").as_int(n.dtype_size));
      n.attrs = nj.get("attrs");
      g.index_of[n.guid] = static_cast<int>(g.nodes.size());
      g.nodes.push_back(std::move(n));
    }
    for (size_t i = 0; i < g.nodes.size(); ++i) {
      for (size_t slot = 0; slot < g.nodes[i].inputs.size(); ++slot) {
        const EdgeRef& r = g.nodes[i].inputs[slot];
        if (r.src_guid >= 0)
          g.consumers[r.src_guid].push_back({static_cast<int>(i),
                                             static_cast<int>(slot)});
      }
    }
    return g;
  }

};

}  // namespace ffsearch
