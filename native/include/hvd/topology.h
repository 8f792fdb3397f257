// Measured link topology: the alpha-beta model behind schedule
// synthesis and measured algorithm selection (ISSUE 13, TACCL-style
// arXiv:2111.04867).
//
// PR 7 seeded ResolveAlgoDefault's selection bands from ONE loopback
// calibration sweep; the bench notes show this box swinging ±30% draw
// to draw, so those bands are wrong on any other machine. This module
// closes the loop: at startup (and on demand) every pair of ranks
// ping-pongs over the EXISTING vectored TCP data connections: small
// and large payload iterations interleave so a scheduler phase shift
// lands on both estimates, and each keeps its best round — producing a
// per-(src, dst) alpha (latency, us) + beta (us per byte) model. Rank
// 0 gathers every rank's measured out-links and broadcasts the full
// matrix, so every rank holds IDENTICAL numbers (the same lockstep
// discipline as the controller param sync the decision rides in on).
//
// The model feeds two consumers:
//  * ResolveAlgoMeasured — cost-models the candidate chunk-schedule
//    tables (ring / striped / hd / doubling) per (payload, np) and
//    replaces the hand-seeded bands whenever a model exists (the
//    bands stay as the fallback and the HOROVOD_TOPOLOGY_PROBE=off
//    path).
//  * tools/synth.py — the sketch-guided schedule search reads the
//    model through hvd_topology and prices candidate tables with the
//    same ScheduleCostUs walk (hvd_schedule_cost_us).
//
// Probing costs ~10 ms per rank pair, so the verdict is cached on
// disk keyed by (hostname, np, local_size): HOROVOD_TOPOLOGY_PROBE=
// auto loads the cache and only measures when it is missing; force
// re-measures and rewrites it; off disables the model entirely.
#pragma once

#include <cstdint>
#include <string>
#include <vector>

#include "hvd/schedule.h"

namespace hvd {

class Controller;

struct TopologyModel {
  int np = 0;                    // 0 = no model
  std::vector<double> alpha_us;  // np*np, [src*np + dst]; 0 on the diag
  std::vector<double> beta_us_per_byte;  // np*np, same layout
  // Job-shape identity the model was measured under ("host|npN|lsM",
  // TopologyHostKey format; rank 0's key on a broadcast blob). The
  // cache layer gates loads on the FULL key; selection
  // (Controller::ResolveAlgoAuto) re-checks the np/ls components
  // against the LIVE world so a model that survived a membership
  // change (elastic restart, Join-shrink) can never serve stale
  // measured verdicts — the hand bands take over until a re-probe.
  std::string hostkey;
  bool valid() const {
    return np > 1 &&
           alpha_us.size() == static_cast<size_t>(np) * np &&
           beta_us_per_byte.size() == static_cast<size_t>(np) * np;
  }
};

// Text serialization (the cache file format AND the sync blob — every
// rank parses the same broadcast string, so the doubles are identical
// by construction). Parse returns an invalid model on any mismatch.
std::string SerializeTopology(const TopologyModel& m,
                              const std::string& hostkey);
TopologyModel ParseTopology(const std::string& blob,
                            const std::string& hostkey_expect);

// Cache identity for this job shape: hostname + np + local_size.
std::string TopologyHostKey(int np, int local_size);
// Do the np/ls components of a stored hostkey match the live world?
// The hostname component is deliberately NOT compared here: it cannot
// change within a process (the cache layer already gates on it), and
// a broadcast blob carries rank 0's hostname, which legitimately
// differs on workers of a multi-host job. An empty key never matches
// (a model without provenance must not serve measured verdicts).
bool TopologyKeyMatchesWorld(const std::string& hostkey, int np,
                             int local_size);
// Cache file path (HOROVOD_TOPOLOGY_CACHE_DIR, default /tmp).
std::string TopologyCachePath(const std::string& hostkey);
// Load iff the file exists, parses, and its hostkey matches.
TopologyModel LoadTopologyCache(const std::string& hostkey);
// Atomic write (tmp + rename) so concurrent jobs never read a torn
// file. Best-effort: failure only costs the next job a re-probe.
void StoreTopologyCache(const TopologyModel& m, const std::string& hostkey);

// Run the pairwise probe rounds over the controller's data
// connections and sync the full matrix (workers send their measured
// out-link rows to rank 0 as one frame each; rank 0 broadcasts the
// assembled blob). MUST run while the data plane is quiet — during
// TcpController::Initialize, or as a collective call with no
// in-flight collectives (the hvd.topology_probe contract). Returns an
// invalid model if any rank's measurement or the sync failed (the
// failure is broadcast, so all ranks agree there is no model).
// `probe_ms_out` (optional) receives this rank's wall-clock cost.
TopologyModel ProbeTopology(Controller* controller, double* probe_ms_out);

// Alpha-beta cost of executing `algo`'s table at `bytes` over the
// full world of `m` (us). Walks every rank's generated table step by
// step: per step, a rank pays the sum of its coalesced per-peer sends
// (alpha + bytes*beta + a per-span overhead) overlapped against its
// slowest receive, and the step costs the slowest rank — the same
// one-SendV/RecvV-per-peer shape ExecuteSchedule actually runs.
// kAlgoDoubling (not a table) is costed analytically as its fold +
// log2 rounds of full-payload exchanges. Returns a huge value for
// algorithms the model cannot price (hier).
double AlgoCostUs(int algo, int64_t bytes, const TopologyModel& m,
                  int stripes, int granularity, int hd_order);

// Generic table pricing for the synthesizer: cost of running
// `per-rank tables` (all P of them, built elsewhere) at `bytes`.
double ScheduleCostUs(const std::vector<ChunkSchedule>& tables,
                      int64_t bytes, const TopologyModel& m);

// Point-to-point pricing for the serving fleet's KV-page migration
// plane (hvd_link_cost_us / hvd_migration_cost_us exports). LinkCostUs
// is one span src -> dst (alpha + bytes*beta, 0 on loopback);
// MigrationCostUs is the chunked generalization — per-chunk
// launch+ack+span overhead, one wire crossing of the payload, plus the
// unoverlappable last-chunk inject. Term-for-term identical to the
// Python twin in horovod_tpu/serve/migrate.py (the sanitizer tier
// cross-checks the pair). Huge value on an invalid model or
// out-of-range rank, so callers gate the same way AlgoCostUs users do.
double LinkCostUs(const TopologyModel& m, int src, int dst, int64_t bytes);
double MigrationCostUs(const TopologyModel& m, int src, int dst,
                       int64_t bytes, int64_t n_chunks);

// Measured replacement for ResolveAlgoDefault: argmin cost over the
// candidate family at the synced synthesis parameters. Defers to the
// hand bands' hier verdict (the loopback model cannot price the
// two-level decomposition) and never returns kAlgoAuto. Falls back to
// ResolveAlgoDefault when the model is missing or np does not match.
int ResolveAlgoMeasured(int64_t bytes, int np, bool hier_ok,
                        int64_t ring_threshold_bytes,
                        const TopologyModel& m, int stripes,
                        int granularity, int hd_order);

// Alltoall family pricing (ISSUE 18): cost of the `algo` (AlltoallAlgo
// space) chunk table at `bytes` — the TOTAL exchanged payload across
// all ranks; the P*P grid splits it uniformly, matching the dense
// equal-splits case the schedule families differ on. Same ScheduleCostUs
// walk as the allreduce candidates.
double AlltoallAlgoCostUs(int algo, int64_t bytes, const TopologyModel& m);

// Measured pairwise-vs-bruck verdict for one alltoall response. Never
// returns kA2aAuto; pairwise (the legacy byte stream) when the model
// is missing or covers a different world. Strict argmin keeps ties on
// pairwise — deterministic on every rank because the model doubles
// are broadcast-identical.
int ResolveAlltoallMeasured(int64_t bytes, int np, const TopologyModel& m);

// Last-probe wall time for the topology_probe_ms gauge, process-wide
// (the topology_links_measured gauge reads the LIVE controller model
// instead — a cache-loaded model measured its links in another job).
double TopologyProbeMs();

}  // namespace hvd
