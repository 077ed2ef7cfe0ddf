// fsxd — the kernel-facing drain daemon (successor of src/fsx_load.py,
// which was a broken 46-line BCC stub: fsx_load.py:15 NameError).
//
// Jobs (SURVEY.md §7.2 "daemon"):
//   1. feature egress: drain per-flow feature records from the kernel's
//      BPF feature ring and republish them into the shared-memory ring
//      the Python/TPU engine consumes;
//   2. verdict ingress: consume blacklist updates from the engine's
//      verdict ring and write them into the kernel blacklist map;
//   3. stand-alone operation: when the TPU plane is absent, the kernel
//      limiter continues alone (fail-open; nothing to do here).
//
// Backends:
//   --sim     in-process traffic generator (no root/NIC; the eBPF-world
//             "fake backend" of SURVEY.md §4) — drives integration tests
//             and benches end-to-end over the real shm transport.
//   --replay  stream fsx_flow_record arrays from a file (pcap-derived).
//   --bpf     the real kernel seam (daemon/fsx_bpf.hpp, raw bpf(2), no
//             libbpf needed): load the FSXPROG image of the assembled
//             XDP fast path, push the config map, optionally attach to
//             an interface and pin under /sys/fs/bpf, then drain the
//             kernel feature ringbuf into the shm ring and apply
//             engine verdicts to the blacklist map.
//
// Key width (--key-bits): 32 writes 48 B fsx_flow_record rings and
// reads 16 B verdicts keyed by the u32 source (an IPv6 source as its
// XOR fold); 128 writes 64 B fsx_flow_record6 rings and reads 32 B
// verdicts keyed by the full source, IPv4 as ::ffff:a.b.c.d (upstream
// src/fsx_struct.h:9 __u128) — the engine's table.key_bits 128.
//
// Output: one JSON line on stdout at exit with counters; progress on
// stderr.  The Python integration test asserts on the JSON.

#include <chrono>
#include <cinttypes>
#include <csignal>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <random>
#include <string>
#include <thread>
#include <unordered_set>
#include <utility>
#include <vector>

#include <net/if.h>

#include "fsx_bpf.hpp"
#include "fsx_schema.h"
#include "shm_ring.hpp"

namespace {

volatile std::sig_atomic_t g_stop = 0;
void on_signal(int) { g_stop = 1; }

uint64_t now_ns() {
    return std::chrono::duration_cast<std::chrono::nanoseconds>(
               std::chrono::steady_clock::now().time_since_epoch())
        .count();
}

// Verdict-ring slots (16 B each), the one size both modes create the
// ring with.  The engine's sink (engine/shm.py ShmVerdictSink) writes
// an update in order and WAITS for room while this daemon's cursor
// moves, so the size decides how often the sink waits, not whether a
// block arrives; what it has to hold is what the sink writes while
// this loop is elsewhere:
//   * one sunk group: the engine admits batches of up to 16,384 records
//     and sinks up to 8 of them as one update (MEGA_AUTO_MAX), and every
//     record can carry a new block: 131,072 blocks at once;
//   * one wave while the reader is away (a restart of this daemon, the
//     drain after it was told to stop): a source holds at most one live
//     block, and BASELINE config 5 states 1M concurrent sources: 2^20.
// 2^20 slots x 16 B = 16 MB of shm covers both (the second holds the
// first 8 times over).  The loops below take 4,096 verdicts an
// iteration, so a live ring stays a few per cent full.
constexpr uint64_t kVerdictRingSlots = 1ull << 20;

struct Options {
    std::string mode = "sim";
    std::string feature_ring = "/tmp/fsx_feature_ring";
    std::string verdict_ring = "/tmp/fsx_verdict_ring";
    // --shards N: fan feature records out over N shm rings by source-IP
    // hash (<feature_ring>.<k>), one per ingest drain worker.  A flow's
    // records land on exactly one shard, so their relative order
    // survives the parallel host ingest stage — the per-CPU-ringbuf
    // production semantics, reproduced at the shm seam.  The verdict
    // ring stays single (verdict volume is tiny and map writes are
    // idempotent).  N=1 keeps the unsuffixed single-ring layout.
    uint32_t shards = 1;
    std::string replay_file;
    uint64_t ring_capacity = 1 << 16;  // feature-ring record slots
    // test hook (--verdict-ring-capacity, not in the usage text): the
    // tests work the sink's wait against a ring smaller than one update
    uint64_t verdict_ring_capacity = kVerdictRingSlots;
    double rate_pps = 1e6;             // sim packet rate
    uint64_t total_packets = 0;        // 0 = unbounded
    double duration_s = 0;             // 0 = unbounded
    double attack_fraction = 0.8;
    double spoof_fraction = 0.0;
    uint32_t n_attack_ips = 64;
    uint32_t n_benign_ips = 1024;
    uint64_t seed = 1;
    // --bpf mode
    std::string iface = "none";        // "none": load + drain, no attach
    std::string prog_image = "kern/build/fsx_prog.img";
    std::string pin_dir;               // e.g. /sys/fs/bpf/fsx ("" = off)
    uint32_t limiter_kind = 0;         // FSX_LIMITER_*
    uint64_t pps_threshold = 1000;     // fsx_kern.c:309 defaults
    uint64_t bps_threshold = 125000000;
    double window_s = 1.0;
    double block_s = 10.0;
    uint64_t bucket_rate_pps = 1000;
    uint64_t bucket_burst = 2000;
    // byte dimension of the token bucket (README.md:153-162
    // bandwidth limit).  Defaults mirror the Python plane's
    // LimiterConfig (125 MB/s, 250 MB burst — the window limiters'
    // byte threshold) so both twins make the same default decisions;
    // pass 0 0 to disable (packet-count only).
    uint64_t bucket_rate_bps = 125000000;
    uint64_t bucket_burst_bytes = 250000000;
    // stateless firewall rules: (packed key, action) pairs from
    // --rule proto:dport (key = (proto << 16) | dport, 0 = wildcard)
    std::vector<std::pair<uint32_t, uint64_t>> rules;
    bool compact = false;              // 16 B kernel-quantized records
    // --key-bits 32|128: the width of a source key on both rings
    uint32_t key_bits = 32;
    // --sim at --key-bits 128: the share of each pool drawn as IPv6
    // /128s inside 2000::/3 (the rest ::ffff:a.b.c.d), and how many
    // IPv6 attackers take the XOR fold of a distinct benign IPv4 source
    double v6_fraction = 0.0;
    uint32_t fold_collide = 0;
    // --pace: sim produces at --rate in REAL time (sleeps when ahead)
    // instead of free-running against ring backpressure.  A real data
    // plane delivers records at line rate, not at memcpy speed; paced
    // mode models that — essential on small hosts where a free-running
    // generator would starve the engine it is feeding.
    bool pace = false;
};

[[noreturn]] void usage(const char *argv0) {
    std::fprintf(stderr,
                 "usage: %s [--sim|--replay FILE|--bpf IFACE] [options]\n"
                 "  --feature-ring PATH   shm feature ring (default /tmp/fsx_feature_ring)\n"
                 "  --verdict-ring PATH   shm verdict ring (default /tmp/fsx_verdict_ring),\n"
                 "                        1048576 slots (16 MB: a block for each of 1M live\n"
                 "                        sources).  The engine's sink writes a full ring in\n"
                 "                        pieces and waits for this daemon to make room; it\n"
                 "                        discards (and counts verdict_ring_dropped) only\n"
                 "                        once the daemon's cursor has stood still for 2 s\n"
                 "  --ring-capacity N     feature ring slots, power of 2 (default 65536)\n"
                 "  --shards N            fan features out over N rings by source-IP\n"
                 "                        hash (<feature-ring>.<k>, one per ingest\n"
                 "                        drain worker; default 1 = single ring)\n"
                 "  --rate PPS            sim packet rate (default 1e6)\n"
                 "  --pace                sim produces at --rate in REAL time\n"
                 "                        (default: free-run vs ring backpressure)\n"
                 "  --packets N           stop after N packets\n"
                 "  --duration S          stop after S seconds\n"
                 "  --attack-fraction F   sim attack share (default 0.8)\n"
                 "  --spoof-fraction F    share of the sim attack records that take a\n"
                 "                        source never seen before and never again\n"
                 "                        (0x80000000 | a 31-bit permutation of their\n"
                 "                        count, keyed by --seed; default 0: none)\n"
                 "  --attack-ips N        sim attack pool (default 64, min 1)\n"
                 "  --benign-ips N        sim benign pool (default 1024, min 1)\n"
                 "  --seed N              sim rng seed\n"
                 "  --key-bits 32|128     source key width on both rings (default 32:\n"
                 "                        48 B records, 16 B verdicts, an IPv6 source\n"
                 "                        as its XOR fold; 128: 64 B records, 32 B\n"
                 "                        verdicts, the full source, IPv4 as ::ffff:a.b.c.d)\n"
                 "  --v6-fraction F       --key-bits 128: share of each sim pool drawn\n"
                 "                        as IPv6 /128s in 2000::/3 (default 0)\n"
                 "  --fold-collide N      --key-bits 128: N IPv6 sim attackers whose XOR\n"
                 "                        fold is the address of a distinct benign IPv4\n"
                 "                        source (default 0)\n"
                 "bpf mode (--bpf IFACE, or --bpf none to load without attach):\n"
                 "  --prog-image PATH     FSXPROG image (default kern/build/fsx_prog.img;\n"
                 "                        emit: python -m flowsentryx_tpu.bpf.image)\n"
                 "  --pin DIR             pin prog+maps under DIR (bpffs, e.g. /sys/fs/bpf/fsx)\n"
                 "  --limiter KIND        fixed|sliding|token (default fixed)\n"
                 "  --pps-threshold N --bps-threshold N --window S --block S\n"
                 "  --bucket-rate N --bucket-burst N\n"
                 "  --bucket-rate-bytes N --bucket-burst-bytes N\n"
                 "                        byte dimension (default 125 MB/s, 250 MB burst; 0 0 = off)\n"
                 "  --rule PROTO:DPORT    stateless drop rule (repeatable;\n"
                 "                        proto any/tcp/udp/icmp[v6]/number,\n"
                 "                        dport 0 = any)\n"
                 "  --compact             16 B kernel-quantized records (the image\n"
                 "                        must be emitted with --compact too)\n",
                 argv0);
    std::exit(2);
}

// Shard index of a folded source address — MUST mirror
// flowsentryx_tpu.core.schema.shard_of (Fibonacci hash) so Python
// tests and tools can predict a flow's shard.
uint32_t fsx_shard_of(uint32_t saddr, uint32_t n) {
    return (uint32_t)((((uint64_t)saddr * 2654435761ULL) >> 16) % n);
}

// A full source, as four u32 loads of its 16 wire bytes (the layout of
// fsx_flow_record6.saddr6 and fsx_verdict_record6.saddr6).
struct Key128 {
    uint32_t w[4];
    bool operator==(const Key128 &o) const {
        return w[0] == o.w[0] && w[1] == o.w[1] && w[2] == o.w[2] &&
               w[3] == o.w[3];
    }
};

// One u32 of a full source for the shard and table hashes — MUST
// mirror flowsentryx_tpu.core.schema.shard_word6: each word times its
// own odd constant, so that no plain XOR fold decides the shard.
uint32_t fsx_shard_word6(const uint32_t w[4]) {
    return (w[0] * 0xC2B2AE3Du) ^ (w[1] * 0x85EBCA77u) ^
           (w[2] * 0x9E3779B1u) ^ w[3];
}

uint32_t key_hash_word(uint32_t k) { return k; }
uint32_t key_hash_word(const Key128 &k) { return fsx_shard_word6(k.w); }

// ::ffff:0:0/96 (RFC 4291 s2.5.5.2) in wire words: an IPv4 source.
constexpr uint32_t kV4MappedWord2 = 0xFFFF0000u;

Key128 v4_mapped(uint32_t saddr) { return Key128{{0, 0, kV4MappedWord2, saddr}}; }

bool is_v4_mapped(const uint32_t w[4]) {
    return w[0] == 0 && w[1] == 0 && w[2] == kV4MappedWord2;
}

std::string shard_path(const std::string &base, uint32_t k, uint32_t n) {
    return n <= 1 ? base : base + "." + std::to_string(k);
}

// N feature rings + the IP-hash router (the --shards fan-out).  The
// router partitions each drained chunk into per-shard lanes first so
// every ring sees one contiguous produce() per chunk, not per record.
class ShardedRings {
public:
    // key128: the source at saddr_off is 16 bytes (fsx_flow_record6)
    ShardedRings(const std::string &base, uint32_t n, uint64_t capacity,
                 size_t rec_size, size_t saddr_off, bool key128 = false)
        : rec_size_(rec_size), saddr_off_(saddr_off), key128_(key128),
          lanes_(n) {
        rings_.reserve(n);
        for (uint32_t k = 0; k < n; k++)
            rings_.push_back(
                fsx::ShmRing::create(shard_path(base, k, n), capacity,
                                     rec_size));
    }

    // Route + push n records; returns how many fit (per-shard rings
    // apply the same fail-open drop policy as the single ring).
    uint64_t produce(const void *records, uint64_t n) {
        const uint32_t ns = (uint32_t)rings_.size();
        if (ns == 1)
            return rings_[0].produce(records, n);
        for (auto &l : lanes_)
            l.clear();
        const char *p = (const char *)records;
        for (uint64_t i = 0; i < n; i++) {
            uint32_t saddr[4];
            std::memcpy(saddr, p + i * rec_size_ + saddr_off_,
                        key128_ ? 16 : 4);
            auto &lane = lanes_[fsx_shard_of(
                key128_ ? fsx_shard_word6(saddr) : saddr[0], ns)];
            lane.insert(lane.end(), p + i * rec_size_,
                        p + (i + 1) * rec_size_);
        }
        uint64_t pushed = 0;
        for (uint32_t k = 0; k < ns; k++)
            if (!lanes_[k].empty())
                pushed += rings_[k].produce(
                    lanes_[k].data(), lanes_[k].size() / rec_size_);
        return pushed;
    }

    uint64_t total_readable() const {
        uint64_t r = 0;
        for (const auto &ring : rings_)
            r += ring.readable();
        return r;
    }

    // Backpressure signal: any shard close to full (a single hot shard
    // must throttle a paced/free-running generator just like the
    // single-ring layout did).
    bool nearly_full(uint64_t margin) const {
        for (const auto &ring : rings_)
            if (ring.readable() >= ring.capacity() - margin)
                return true;
        return false;
    }

private:
    size_t rec_size_, saddr_off_;
    bool key128_;
    std::vector<std::vector<char>> lanes_;
    std::vector<fsx::ShmRing> rings_;
};

// Per-CPU map lookups copy one value per POSSIBLE cpu into the user
// buffer; undersizing it is a kernel write past the end (heap smash).
// Parse list format ("0-3,5-7") by the highest id seen, and never
// return less than the libc view of configured CPUs.
uint32_t n_possible_cpus() {
    long conf = ::sysconf(_SC_NPROCESSORS_CONF);
    uint32_t best = conf > 0 ? (uint32_t)conf : 1;
    FILE *f = std::fopen("/sys/devices/system/cpu/possible", "r");
    if (!f)
        return best;
    char buf[256] = {0};
    if (std::fgets(buf, sizeof(buf), f)) {
        for (char *tok = std::strtok(buf, ","); tok;
             tok = std::strtok(nullptr, ",")) {
            const char *dash = std::strchr(tok, '-');
            uint32_t hi = (uint32_t)std::strtoul(dash ? dash + 1 : tok,
                                                 nullptr, 10);
            if (hi + 1 > best)
                best = hi + 1;
        }
    }
    std::fclose(f);
    return best;
}

// Aggregate the per-CPU stats map into one struct fsx_stats.
fsx_stats read_stats(int stats_fd) {
    fsx_stats total{};
    uint32_t ncpu = n_possible_cpus();
    std::vector<fsx_stats> per(ncpu);
    uint32_t zero = 0;
    if (fsxbpf::map_lookup(stats_fd, &zero, per.data()) == 0) {
        for (const auto &s : per) {
            total.allowed += s.allowed;
            total.dropped_blacklist += s.dropped_blacklist;
            total.dropped_rate += s.dropped_rate;
            total.dropped_ml += s.dropped_ml;
            total.dropped_rule += s.dropped_rule;
            // kernel-distilled classifier bands (ml=True images; zero
            // on non-ml images or while no model blob is pushed)
            total.ml_pass += s.ml_pass;
            total.ml_escalated += s.ml_escalated;
        }
    }
    return total;
}

// A verdict into the kernel's blacklist.  A 32-bit key (an IPv4 source,
// or the XOR fold of an IPv6 one) goes to blacklist_map.  A full
// source goes by its kind: v4-mapped to blacklist_map under its IPv4
// address, IPv6 to blacklist_v6 under all 16 bytes (upstream
// src/fsx_kern.c:66-72,159-176) — never under its fold, which a host
// choosing its /64's low bits can make equal an innocent IPv4 address.
void write_block(int bl_fd, int, const fsx_verdict_record &v) {
    uint32_t saddr = v.saddr;
    uint64_t until = v.until_ns;
    fsxbpf::map_update(bl_fd, &saddr, &until);
}

void write_block(int bl_fd, int bl6_fd, const fsx_verdict_record6 &v) {
    uint32_t w[4];
    std::memcpy(w, v.saddr6, sizeof(w));
    uint64_t until = v.until_ns;
    if (is_v4_mapped(w))
        fsxbpf::map_update(bl_fd, &w[3], &until);
    else
        fsxbpf::map_update(bl6_fd, w, &until);
}

// --bpf backend: the real kernel seam (jobs 1+2 of the header comment).
// VRec is the verdict record of --key-bits (16 B or 32 B).
template <class VRec>
int run_bpf(const Options &o) {
    auto lp = fsxbpf::load_image(o.prog_image);
    if (!lp.error.empty()) {
        std::fprintf(stderr, "fsxd: bpf load failed: %s\n", lp.error.c_str());
        return 1;
    }
    std::fprintf(stderr, "fsxd: program loaded through verifier (fd %d), %zu maps\n",
                 lp.prog_fd, lp.map_fds.size());

    // Push runtime policy into the config map (the capability the
    // reference hard-coded at fsx_kern.c:308-310).
    fsx_config cfg{};
    cfg.limiter_kind = o.limiter_kind;
    cfg.valid = 1;
    cfg.pps_threshold = o.pps_threshold;
    cfg.bps_threshold = o.bps_threshold;
    cfg.window_ns = (uint64_t)(o.window_s * 1e9);
    cfg.block_ns = (uint64_t)(o.block_s * 1e9);
    cfg.bucket_rate_pps = o.bucket_rate_pps;
    cfg.bucket_burst = o.bucket_burst;
    cfg.bucket_rate_bps = o.bucket_rate_bps;
    cfg.bucket_burst_bytes = o.bucket_burst_bytes;
    cfg.rule_count = o.rules.size();
    uint32_t zero = 0;
    if (fsxbpf::map_update(lp.map_fd("config_map"), &zero, &cfg) < 0) {
        std::perror("fsxd: config_map update");
        return 1;
    }
    for (const auto &r : o.rules) {
        uint32_t key = r.first;
        uint64_t act = r.second;
        if (fsxbpf::map_update(lp.map_fd("rule_map"), &key, &act) < 0) {
            std::perror("fsxd: rule_map update");
            return 1;
        }
    }
    if (!o.rules.empty())
        std::fprintf(stderr, "fsxd: %zu firewall rule(s) pushed\n",
                     o.rules.size());

    int link_fd = -1;
    if (o.iface != "none") {
        unsigned ifindex = if_nametoindex(o.iface.c_str());
        if (!ifindex) {
            std::fprintf(stderr, "fsxd: unknown interface %s\n",
                         o.iface.c_str());
            return 1;
        }
        link_fd = fsxbpf::link_create_xdp(lp.prog_fd, (int)ifindex);
        if (link_fd < 0) {
            std::perror("fsxd: XDP link_create");
            return 1;
        }
        std::fprintf(stderr, "fsxd: XDP attached to %s (ifindex %u)\n",
                     o.iface.c_str(), ifindex);
    }

    if (!o.pin_dir.empty()) {
        ::mkdir(o.pin_dir.c_str(), 0755);
        if (fsxbpf::obj_pin(lp.prog_fd, o.pin_dir + "/prog") < 0)
            std::perror("fsxd: pin prog");
        for (size_t i = 0; i < lp.map_fds.size(); i++)
            if (fsxbpf::obj_pin(lp.map_fds[i],
                                o.pin_dir + "/" + lp.map_specs[i].name) < 0)
                std::perror("fsxd: pin map");
        std::fprintf(stderr, "fsxd: pinned under %s\n", o.pin_dir.c_str());
    }

    // --key-bits 128 takes 64 B records from the kernel ring: an image
    // that emits 48 B ones trips the record-size check below
    const bool key128 = o.key_bits == 128;
    const size_t rec_size = o.compact ? sizeof(fsx_compact_record)
                            : key128  ? sizeof(fsx_flow_record6)
                                      : sizeof(fsx_flow_record);
    ShardedRings frings(o.feature_ring, o.shards, o.ring_capacity, rec_size,
                        o.compact ? 0
                        : key128  ? offsetof(fsx_flow_record6, saddr6)
                                  : offsetof(fsx_flow_record, saddr),
                        key128);
    auto vring = fsx::ShmRing::create(o.verdict_ring, o.verdict_ring_capacity,
                                      sizeof(VRec));

    const fsxbpf::ImageMapSpec *rspec = lp.spec("feature_ring");
    fsxbpf::RingbufConsumer rb;
    if (!rspec || !rb.open(lp.map_fd("feature_ring"), rspec->max_entries)) {
        std::fprintf(stderr, "fsxd: ringbuf mmap failed\n");
        return 1;
    }

    int blacklist_fd = lp.map_fd("blacklist_map");
    int blacklist_v6_fd = lp.map_fd("blacklist_v6");
    int stats_fd = lp.map_fd("stats_map");
    uint64_t forwarded = 0, dropped_ring_full = 0, verdicts = 0;
    bool size_warned = false, first_interval_done = false;
    std::vector<uint8_t> buf;
    std::vector<VRec> vbatch(4096);
    uint64_t t_start = now_ns(), next_report = t_start + 1'000'000'000ULL;

    while (!g_stop) {
        // 1. feature egress: kernel ringbuf → shm ring
        buf.clear();
        size_t n = rb.drain(buf, rec_size, 4096);
        if (n) {
            uint64_t pushed = frings.produce(buf.data(), n);
            dropped_ring_full += n - pushed;
            forwarded += pushed;
        }
        if (rb.skipped && !size_warned) {
            size_warned = true;
            std::fprintf(stderr,
                         "fsxd: WARNING: kernel ring records do not match "
                         "the configured %zu-byte size — the loaded image's "
                         "emit format disagrees with %s (records are being "
                         "dropped)\n",
                         rec_size, o.compact ? "--compact"
                                   : key128  ? "--key-bits 128"
                                             : "48 B default");
        }
        // 2. verdict ingress: shm ring → blacklist map
        uint64_t nv = vring.consume(vbatch.data(), vbatch.size());
        for (uint64_t i = 0; i < nv; i++)
            write_block(blacklist_fd, blacklist_v6_fd, vbatch[i]);
        verdicts += nv;

        uint64_t t = now_ns();
        if (o.duration_s > 0 &&
            (t - t_start) > (uint64_t)(o.duration_s * 1e9))
            break;
        if (t >= next_report) {
            fsx_stats s = read_stats(stats_fd);
            std::fprintf(stderr,
                         "fsxd: forwarded=%" PRIu64 " verdicts=%" PRIu64
                         " skipped=%" PRIu64
                         " allowed=%" PRIu64 " drop_bl=%" PRIu64
                         " drop_rate=%" PRIu64
                         " drop_ml=%" PRIu64 " ml_pass=%" PRIu64
                         " ml_esc=%" PRIu64 "\n",
                         forwarded, verdicts, rb.skipped, (uint64_t)s.allowed,
                         (uint64_t)s.dropped_blacklist,
                         (uint64_t)s.dropped_rate,
                         (uint64_t)s.dropped_ml, (uint64_t)s.ml_pass,
                         (uint64_t)s.ml_escalated);
            // A record-size mismatch drops EVERY drained record: the
            // deployment looks alive (kernel counters move) while the
            // ML plane starves.  The first interval that drains
            // anything decides: 100% skips means misconfiguration, not
            // traffic — fail fast instead of warning once and running
            // forever.
            if (!first_interval_done && forwarded + rb.skipped > 0) {
                first_interval_done = true;
                if (forwarded == 0 && rb.skipped > 0) {
                    std::fprintf(stderr,
                                 "fsxd: FATAL: 100%% of kernel records "
                                 "skipped (record-size mismatch between "
                                 "the loaded image and %s); exiting\n",
                                 o.compact ? "--compact"
                                 : key128  ? "--key-bits 128"
                                           : "the 48 B default");
                    return 2;
                }
            }
            next_report = t + 1'000'000'000ULL;
        }
        if (n == 0 && nv == 0)
            std::this_thread::sleep_for(std::chrono::microseconds(200));
    }

    // final verdict drain, to the last verdict the ring holds (mirrors
    // the sim path's exit contract)
    while (uint64_t extra = vring.consume(vbatch.data(), vbatch.size())) {
        for (uint64_t i = 0; i < extra; i++)
            write_block(blacklist_fd, blacklist_v6_fd, vbatch[i]);
        verdicts += extra;
    }

    fsx_stats s = read_stats(stats_fd);
    std::printf("{\"produced\": %" PRIu64 ", \"verdicts\": %" PRIu64
                ", \"dropped_ring_full\": %" PRIu64
                ", \"skipped\": %" PRIu64
                ", \"allowed\": %" PRIu64 ", \"dropped_blacklist\": %" PRIu64
                ", \"dropped_rate\": %" PRIu64 ", \"dropped_ml\": %" PRIu64
                ", \"dropped_rule\": %" PRIu64
                ", \"ml_pass\": %" PRIu64 ", \"ml_escalated\": %" PRIu64
                "}\n",
                forwarded, verdicts, dropped_ring_full, rb.skipped,
                (uint64_t)s.allowed,
                (uint64_t)s.dropped_blacklist, (uint64_t)s.dropped_rate,
                (uint64_t)s.dropped_ml, (uint64_t)s.dropped_rule,
                (uint64_t)s.ml_pass, (uint64_t)s.ml_escalated);
    if (link_fd >= 0)
        ::close(link_fd);
    return 0;
}

Options parse(int argc, char **argv) {
    Options o;
    for (int i = 1; i < argc; i++) {
        std::string a = argv[i];
        auto next = [&]() -> std::string {
            if (++i >= argc)
                usage(argv[0]);
            return argv[i];
        };
        if (a == "--compact")
            o.compact = true;
        else if (a == "--sim")
            o.mode = "sim";
        else if (a == "--replay") {
            o.mode = "replay";
            o.replay_file = next();
        } else if (a == "--bpf") {
            o.mode = "bpf";
            o.iface = next();  // interface name, or "none" (no attach)
        } else if (a == "--prog-image")
            o.prog_image = next();
        else if (a == "--pin")
            o.pin_dir = next();
        else if (a == "--limiter") {
            std::string k = next();
            o.limiter_kind = k == "sliding" ? FSX_LIMITER_SLIDING_WINDOW
                             : k == "token" ? FSX_LIMITER_TOKEN_BUCKET
                                            : FSX_LIMITER_FIXED_WINDOW;
        } else if (a == "--pps-threshold")
            o.pps_threshold = std::stoull(next());
        else if (a == "--bps-threshold")
            o.bps_threshold = std::stoull(next());
        else if (a == "--window")
            o.window_s = std::stod(next());
        else if (a == "--block")
            o.block_s = std::stod(next());
        else if (a == "--bucket-rate")
            o.bucket_rate_pps = std::stoull(next());
        else if (a == "--bucket-burst")
            o.bucket_burst = std::stoull(next());
        else if (a == "--bucket-rate-bytes")
            o.bucket_rate_bps = std::stoull(next());
        else if (a == "--bucket-burst-bytes")
            o.bucket_burst_bytes = std::stoull(next());
        else if (a == "--rule") {
            std::string spec = next();
            auto colon = spec.find(':');
            if (colon == std::string::npos)
                usage(argv[0]);
            std::string p = spec.substr(0, colon);
            uint32_t proto;
            if (p == "any") proto = 0;
            else if (p == "icmp") proto = 1;
            else if (p == "tcp") proto = 6;
            else if (p == "udp") proto = 17;
            else if (p == "icmpv6") proto = 58;
            else {
                try {
                    proto = (uint32_t)std::stoul(p);
                } catch (const std::exception &) {
                    usage(argv[0]);
                }
            }
            uint32_t dport;
            try {
                dport = (uint32_t)std::stoul(spec.substr(colon + 1));
            } catch (const std::exception &) {
                usage(argv[0]);
            }
            if (proto > 255 || dport > 65535 || (proto == 0 && dport == 0))
                usage(argv[0]);
            o.rules.emplace_back((proto << 16) | dport, 1 /*FSX_RULE_DROP*/);
        }
        else if (a == "--feature-ring")
            o.feature_ring = next();
        else if (a == "--verdict-ring")
            o.verdict_ring = next();
        else if (a == "--ring-capacity")
            o.ring_capacity = std::stoull(next());
        else if (a == "--verdict-ring-capacity")
            o.verdict_ring_capacity = std::stoull(next());
        else if (a == "--shards")
            o.shards = (uint32_t)std::stoul(next());
        else if (a == "--rate")
            o.rate_pps = std::stod(next());
        else if (a == "--pace")
            o.pace = true;
        else if (a == "--packets")
            o.total_packets = std::stoull(next());
        else if (a == "--duration")
            o.duration_s = std::stod(next());
        else if (a == "--attack-fraction")
            o.attack_fraction = std::stod(next());
        else if (a == "--spoof-fraction")
            o.spoof_fraction = std::stod(next());
        else if (a == "--attack-ips")
            o.n_attack_ips = (uint32_t)std::stoul(next());
        else if (a == "--benign-ips")
            o.n_benign_ips = (uint32_t)std::stoul(next());
        else if (a == "--seed")
            o.seed = std::stoull(next());
        else if (a == "--key-bits")
            o.key_bits = (uint32_t)std::stoul(next());
        else if (a == "--v6-fraction")
            o.v6_fraction = std::stod(next());
        else if (a == "--fold-collide")
            o.fold_collide = (uint32_t)std::stoul(next());
        else
            usage(argv[0]);
    }
    if (o.key_bits != 32 && o.key_bits != 128) {
        std::fprintf(stderr, "fsxd: --key-bits must be 32 or 128\n");
        std::exit(1);
    }
    if (!(o.v6_fraction >= 0.0 && o.v6_fraction <= 1.0)) {
        std::fprintf(stderr, "fsxd: --v6-fraction must be in [0, 1]\n");
        std::exit(1);
    }
    if (o.key_bits == 32 && (o.v6_fraction > 0 || o.fold_collide > 0)) {
        std::fprintf(stderr, "fsxd: --v6-fraction and --fold-collide need "
                     "--key-bits 128 (a 32-bit key holds no IPv6 source)\n");
        std::exit(1);
    }
    if (o.key_bits == 128 && o.compact) {
        std::fprintf(stderr, "fsxd: --compact records carry a 32-bit "
                     "source: not with --key-bits 128\n");
        std::exit(1);
    }
    if ((o.bucket_rate_bps == 0) != (o.bucket_burst_bytes == 0)) {
        std::fprintf(stderr, "fsxd: --bucket-rate-bytes and "
                     "--bucket-burst-bytes must be both zero or both "
                     "positive\n");
        std::exit(1);
    }
    if (o.shards < 1 || o.shards > 64) {
        std::fprintf(stderr, "fsxd: --shards must be in [1, 64]\n");
        std::exit(1);
    }
    if (!(o.spoof_fraction >= 0.0 && o.spoof_fraction <= 1.0)) {
        std::fprintf(stderr, "fsxd: --spoof-fraction must be in [0, 1]\n");
        std::exit(1);
    }
    if (o.n_attack_ips == 0 || o.n_benign_ips == 0) {
        // SimSource indexes each pool with rng() % size: an empty pool
        // is a modulo-by-zero SIGFPE on the first record of that class.
        std::fprintf(stderr,
                     "fsxd: --attack-ips and --benign-ips must be >= 1\n");
        std::exit(1);
    }
    return o;
}

// std::mt19937_64, draw for draw (same seeding, twist and tempering:
// a seed gives the records it always gave), written out because
// libstdc++'s costs 7.7 ns a draw at -O2 against 2.5 for this, and a
// sim record takes nine: the generator was the ceiling of the
// benchmark's closed-loop cells (PERF.md section 6, PR 38).
class Mt64 {
public:
    using result_type = uint64_t;
    static constexpr result_type min() { return 0; }
    static constexpr result_type max() { return ~0ULL; }

    explicit Mt64(uint64_t seed) {
        mt_[0] = seed;
        for (int i = 1; i < N; i++)
            mt_[i] = 6364136223846793005ULL *
                         (mt_[i - 1] ^ (mt_[i - 1] >> 62)) + (uint64_t)i;
    }

    result_type operator()() {
        if (next_ >= N)
            refill();
        uint64_t x = mt_[next_++];
        x ^= (x >> 29) & 0x5555555555555555ULL;
        x ^= (x << 17) & 0x71D67FFFEDA60000ULL;
        x ^= (x << 37) & 0xFFF7EEE000000000ULL;
        return x ^ (x >> 43);
    }

private:
    static constexpr int N = 312, M = 156;

    static uint64_t twist(uint64_t u, uint64_t v) {
        uint64_t x = (u & 0xFFFFFFFF80000000ULL) | (v & 0x7FFFFFFFULL);
        return (x >> 1) ^ ((v & 1) ? 0xB5026F5AA96619E9ULL : 0);
    }

    void refill() {
        int i = 0;
        for (; i < N - M; i++)
            mt_[i] = mt_[i + M] ^ twist(mt_[i], mt_[i + 1]);
        for (; i < N - 1; i++)
            mt_[i] = mt_[i + M - N] ^ twist(mt_[i], mt_[i + 1]);
        mt_[N - 1] = mt_[M - 1] ^ twist(mt_[N - 1], mt_[0]);
        next_ = 0;
    }

    uint64_t mt_[N];
    int next_ = N;
};

// The sim path's blocked sources, key -> until_ns: open addressing,
// linear probing, 16-byte slots for a u32 key and 32-byte ones for a
// Key128 (a lookup is one cache line; the std::unordered_map this
// replaces chased nodes for a fifth of the generator's time at half a
// million entries).  As with that map's lazy erase, an entry counts in
// size() until a lookup finds it expired.  A slot is never freed: the
// table holds every source ever blocked, which the sim pools bound.
// A Key128 table keys by all 128 bits, so two sources that share an
// XOR fold are suppressed apart.
template <class K>
class Blacklist {
public:
    Blacklist() : slots_(1 << 12) {}

    void block(const K &key, uint64_t until_ns) {
        if ((used_ + 1) * 2 > slots_.size())
            grow();
        Slot &s = find(key);
        if (!s.used) {
            s.used = 1;
            s.key = key;
            used_++;
        }
        if (!s.live) {
            s.live = 1;
            live_++;
        }
        s.until_ns = until_ns;
    }

    // true while `key` is blocked at `now_ns`; the lookup that finds
    // its block expired drops it
    bool blocked(const K &key, uint64_t now_ns) {
        Slot &s = find(key);
        if (!s.used || !s.live)
            return false;
        if (now_ns < s.until_ns)
            return true;
        s.live = 0;
        live_--;
        return false;
    }

    size_t size() const { return live_; }

private:
    struct Slot {
        K key{};
        uint16_t used = 0, live = 0;
        uint64_t until_ns = 0;
    };

    Slot &find(const K &key) {
        // the shard router's Fibonacci hash (fsx_shard_of), masked
        size_t mask = slots_.size() - 1;
        size_t i = (((uint64_t)key_hash_word(key) * 2654435761ULL) >> 16) &
                   mask;
        while (slots_[i].used && !(slots_[i].key == key))
            i = (i + 1) & mask;
        return slots_[i];
    }

    void grow() {
        std::vector<Slot> old(slots_.size() * 2);
        old.swap(slots_);
        for (const Slot &s : old)
            if (s.used)
                find(s.key) = s;
    }

    std::vector<Slot> slots_;
    size_t used_ = 0, live_ = 0;
};

// Minimal mirror of the Python TrafficGen's statistics so --sim produces
// model-meaningful features (flowsentryx_tpu/engine/traffic.py is the
// reference implementation; both emit kernel-estimator-style records).
class SimSource {
public:
    explicit SimSource(const Options &o) : o_(o), rng_(o.seed) {
        attack_ips_.resize(o.n_attack_ips);
        benign_ips_.resize(o.n_benign_ips);
        std::uniform_int_distribution<uint32_t> low(1, (1u << 24) - 1);
        for (auto &ip : attack_ips_)
            ip = low(rng_);
        for (auto &ip : benign_ips_)
            ip = (1u << 24) + low(rng_);
        clock_ns_ = 1'000'000'000ULL;
        dt_ns_ = (uint64_t)(1e9 / o.rate_pps);
        if (dt_ns_ == 0)
            dt_ns_ = 1;
        // perm31's key, from the seed alone (not from rng_: with
        // --spoof-fraction 0 a seed gives the draws it always gave)
        uint64_t k = (o.seed + 0x9E3779B97F4A7C15ULL) * 0xBF58476D1CE4E5B9ULL;
        k ^= k >> 31;
        spoof_mul_[0] = (uint32_t)k | 1;
        spoof_mul_[1] = (uint32_t)(k >> 32) | 1;
        spoof_xor_ = (uint32_t)(k >> 17) & kMask31;
        if (o.key_bits == 128)
            draw_keys128();
    }

    uint64_t spoofed() const { return spoofed_; }
    uint64_t v6_sources() const { return v6_sources_; }
    uint64_t planted() const { return planted_; }

    // 64 B records: the 48 B ones the same seed gives, each source
    // widened to its pool entry's full address (a spoofed one to its
    // v4-mapped form) and an IPv6 one flagged FSX_FLAG_IPV6.
    void fill(std::vector<fsx_flow_record6> &out, size_t n) {
        fill(narrow_, n);
        out.resize(n);
        const size_t na = attack_keys_.size();
        for (size_t i = 0; i < n; i++) {
            const fsx_flow_record &r = narrow_[i];
            fsx_flow_record6 &w = out[i];
            std::memset(&w, 0, sizeof(w));
            uint32_t k = pool_idx_[i];
            Key128 key = k == kNoPool ? v4_mapped(r.saddr)
                         : k < na     ? attack_keys_[k]
                                      : benign_keys_[k - na];
            w.ts_ns = r.ts_ns;
            std::memcpy(w.saddr6, key.w, sizeof(key.w));
            w.pkt_len = r.pkt_len;
            w.ip_proto = r.ip_proto;
            w.flags = r.flags | (is_v4_mapped(key.w) ? 0 : FSX_FLAG_IPV6);
            std::memcpy(w.feat, r.feat, sizeof(w.feat));
        }
    }

    void fill(std::vector<fsx_flow_record> &out, size_t n) {
        out.resize(n);
        if (key128_)
            pool_idx_.resize(n);
        std::uniform_real_distribution<double> u01(0.0, 1.0);
        for (size_t i = 0; i < n; i++) {
            fsx_flow_record &r = out[i];
            std::memset(&r, 0, sizeof(r));
            bool attack = u01(rng_) < o_.attack_fraction;
            r.ts_ns = clock_ns_;
            clock_ns_ += dt_ns_;
            // Feature slots follow core/schema.py FEATURE_NAMES: 3/4
            // are flow_duration_ms / flow_pps_x1000 (the r5 flow-age
            // slots), NOT the pre-r5 variance/avg-size pair.
            if (attack) {
                // the extra draw is made only where the option is on
                if (o_.spoof_fraction > 0 &&
                    u01(rng_) < o_.spoof_fraction) {
                    r.saddr = next_spoofed();
                    if (key128_)
                        pool_idx_[i] = kNoPool;
                } else {
                    uint32_t k = (uint32_t)(rng_() % attack_ips_.size());
                    r.saddr = attack_ips_[k];
                    if (key128_)
                        pool_idx_[i] = k;
                }
                r.pkt_len = 60 + rng_() % 20;
                r.ip_proto = 17;  // UDP flood
                r.feat[0] = 80;
                uint32_t size = r.pkt_len;
                r.feat[1] = size;
                r.feat[2] = rng_() % 3;
                uint64_t iat = 1 + rng_() % 50;  // µs: flood arrivals
                uint64_t npkts = 100 + rng_() % 4900;
                uint64_t dur_us = std::max<uint64_t>(iat * npkts, 1);
                r.feat[3] = (uint32_t)(dur_us / 1000);
                r.feat[4] = (uint32_t)std::min<uint64_t>(
                    npkts * 1'000'000'000ULL / dur_us, 0xFFFFFFFFULL);
                r.feat[5] = (uint32_t)iat;
                r.feat[6] = rng_() % 20;
                r.feat[7] = (uint32_t)(iat * (1 + rng_() % 3));
            } else {
                uint32_t k = (uint32_t)(rng_() % benign_ips_.size());
                r.saddr = benign_ips_[k];
                if (key128_)
                    pool_idx_[i] = (uint32_t)attack_ips_.size() + k;
                r.pkt_len = 100 + rng_() % 1400;
                r.ip_proto = 6;
                r.flags = FSX_FLAG_TCP;
                r.feat[0] = 443;
                uint32_t size = r.pkt_len;
                uint32_t std_ = 100 + rng_() % 500;
                r.feat[1] = size;
                r.feat[2] = std_;
                uint64_t iat = 5'000 + rng_() % 495'000;  // µs: human-scale
                uint64_t npkts = 2 + rng_() % 198;
                uint64_t dur_us = std::max<uint64_t>(iat * npkts, 1);
                r.feat[3] = (uint32_t)(dur_us / 1000);
                r.feat[4] = (uint32_t)std::min<uint64_t>(
                    npkts * 1'000'000'000ULL / dur_us, 0xFFFFFFFFULL);
                r.feat[5] = (uint32_t)iat;
                r.feat[6] = (uint32_t)(iat / (1 + rng_() % 3));
                r.feat[7] = (uint32_t)(iat * (2 + rng_() % 6));
            }
        }
    }

private:
    static constexpr uint32_t kMask31 = 0x7FFFFFFFu;
    static constexpr uint32_t kNoPool = 0xFFFFFFFFu;  // a spoofed source

    // --key-bits 128: each pool entry's full source.  The first
    // round(--v6-fraction x size) entries of a pool are IPv6 /128s in
    // 2000::/3 (first byte 0x20-0x3F), the rest ::ffff:a.b.c.d of the
    // entry's IPv4 address.  Then the plant: IPv6 attacker i <
    // --fold-collide takes as its last word the one that makes its
    // XOR fold (kern/parsing.h fsx_fold_ip6) the address of the i-th
    // distinct benign IPv4 source.  Drawn from the seed by a generator
    // of their own, so the pools' draws stay those of --key-bits 32.
    void draw_keys128() {
        key128_ = true;
        Mt64 r(o_.seed ^ 0xD1B54A32D192ED03ULL);
        auto widen = [&](const std::vector<uint32_t> &ips,
                         std::vector<Key128> &keys) {
            size_t n6 = (size_t)std::llround(o_.v6_fraction * ips.size());
            keys.resize(ips.size());
            for (size_t i = 0; i < ips.size(); i++) {
                if (i >= n6) {
                    keys[i] = v4_mapped(ips[i]);
                    continue;
                }
                uint64_t a = r(), b = r();
                keys[i] = Key128{{((uint32_t)a & ~0xE0u) | 0x20u,
                                  (uint32_t)(a >> 32), (uint32_t)b,
                                  (uint32_t)(b >> 32)}};
            }
            v6_sources_ += n6;
            return n6;
        };
        size_t a6 = widen(attack_ips_, attack_keys_);
        widen(benign_ips_, benign_keys_);
        std::unordered_set<uint32_t> taken;
        size_t j = 0;
        for (uint32_t i = 0; i < o_.fold_collide; i++) {
            while (j < benign_keys_.size() &&
                   (!is_v4_mapped(benign_keys_[j].w) ||
                    !taken.insert(benign_ips_[j]).second))
                j++;
            if (i >= a6 || j == benign_keys_.size()) {
                std::fprintf(stderr,
                             "fsxd: --fold-collide %u: only %u IPv6 "
                             "attackers with a distinct benign IPv4 fold\n",
                             o_.fold_collide, i);
                std::exit(1);
            }
            uint32_t *w = attack_keys_[i].w;
            w[3] = benign_ips_[j++] ^ w[0] ^ w[1] ^ w[2];
            planted_++;
        }
    }

    // A bijection of the 31-bit numbers: multiplying by an odd number
    // modulo 2^31, xor with a constant and xor with a right shift of
    // itself are each one.
    uint32_t perm31(uint32_t x) const {
        x = (x * spoof_mul_[0]) & kMask31;
        x ^= x >> 15;
        x ^= spoof_xor_;
        x = (x * spoof_mul_[1]) & kMask31;
        x ^= x >> 13;
        return x;
    }

    // The source of the next spoofed record: the top bit (the pools
    // lie below 2^25) over perm31 of a counter, so none recurs within
    // 2^31 records, none is a pooled source or key 0, and the one
    // count that would give 0xFFFFFFFF (the engine's invalid key) is
    // passed over.
    uint32_t next_spoofed() {
        uint32_t x;
        do
            x = perm31((uint32_t)(spoof_n_++) & kMask31);
        while (x == kMask31);
        spoofed_++;
        return 0x80000000u | x;
    }

    Options o_;
    Mt64 rng_;
    std::vector<uint32_t> attack_ips_, benign_ips_;
    bool key128_ = false;
    std::vector<Key128> attack_keys_, benign_keys_;
    std::vector<uint32_t> pool_idx_;     // per record of the last fill
    std::vector<fsx_flow_record> narrow_;
    uint64_t v6_sources_ = 0, planted_ = 0;
    uint64_t clock_ns_, dt_ns_;
    uint32_t spoof_mul_[2], spoof_xor_;
    uint64_t spoof_n_ = 0, spoofed_ = 0;
};

uint32_t key_of(const fsx_flow_record &r) { return r.saddr; }
uint32_t key_of(const fsx_verdict_record &v) { return v.saddr; }

Key128 key_of(const fsx_flow_record6 &r) {
    Key128 k;
    std::memcpy(k.w, r.saddr6, sizeof(k.w));
    return k;
}

Key128 key_of(const fsx_verdict_record6 &v) {
    Key128 k;
    std::memcpy(k.w, v.saddr6, sizeof(k.w));
    return k;
}

// --sim / --replay: Rec and VRec are the feature and verdict records
// of --key-bits (48 B and 16 B, or 64 B and 32 B).
template <class Rec, class VRec>
int run_stream(const Options &o) {
    using Key = decltype(key_of(std::declval<Rec>()));
    constexpr bool key128 = sizeof(Rec) == sizeof(fsx_flow_record6);
    ShardedRings frings(o.feature_ring, o.shards, o.ring_capacity,
                        sizeof(Rec),
                        key128 ? offsetof(fsx_flow_record6, saddr6)
                               : offsetof(fsx_flow_record, saddr),
                        key128);
    auto vring = fsx::ShmRing::create(o.verdict_ring, o.verdict_ring_capacity,
                                      sizeof(VRec));

    std::fprintf(stderr,
                 "fsxd: mode=%s feature_ring=%s shards=%u verdict_ring=%s\n",
                 o.mode.c_str(), o.feature_ring.c_str(), o.shards,
                 o.verdict_ring.c_str());

    uint64_t produced = 0, dropped_ring_full = 0, verdicts = 0, suppressed = 0;
    Blacklist<Key> blacklist;

    FILE *replay = nullptr;
    if (o.mode == "replay") {
        replay = std::fopen(o.replay_file.c_str(), "rb");
        if (!replay) {
            std::perror("fsxd: open replay file");
            return 1;
        }
    }

    SimSource sim(o);
    std::vector<Rec> batch;
    std::vector<VRec> vbatch(4096);
    const size_t CHUNK = 2048;
    uint64_t t_start = now_ns();
    uint64_t next_report = t_start + 1'000'000'000ULL;
    uint64_t drain_deadline = 0;  // set once total_packets is reached

    while (!g_stop) {
        // ---- produce features -------------------------------------------
        size_t want = CHUNK;
        if (o.pace) {
            // Real-time pacing: never run ahead of rate_pps × elapsed.
            // Sleep in small slices so verdict ingress stays responsive.
            uint64_t target =
                (uint64_t)((double)(now_ns() - t_start) * o.rate_pps / 1e9);
            if (produced >= target) {
                std::this_thread::sleep_for(std::chrono::microseconds(100));
                target = (uint64_t)((double)(now_ns() - t_start) *
                                    o.rate_pps / 1e9);
            }
            // Catch-up cap of 8 chunks, not 1: on a contended host the
            // 100 µs sleep stretches to ~1 ms, and a single-CHUNK cap
            // silently clips the offered rate to CHUNK per wake-up
            // (~2 Mpps) — a paced source must be allowed to burst back
            // to schedule, like a real NIC queue after a stall.
            want = produced < target
                       ? std::min<uint64_t>(8 * CHUNK, target - produced)
                       : 0;
        }
        if (o.total_packets && produced + want > o.total_packets)
            want = o.total_packets - produced;
        if (want > 0) {
            if (replay) {
                batch.resize(want);
                size_t got = std::fread(batch.data(), sizeof(Rec), want,
                                        replay);
                batch.resize(got);
                if (got == 0)
                    g_stop = 1;
            } else {
                sim.fill(batch, want);
            }

            // Blacklist suppression: records from blocked sources never
            // reach the engine (the sim analog of XDP_DROP).
            uint64_t tnow = batch.empty() ? 0 : batch.back().ts_ns;
            size_t w = 0;
            for (size_t i = 0; i < batch.size(); i++) {
                if (blacklist.blocked(key_of(batch[i]), tnow)) {
                    suppressed++;
                    continue;
                }
                if (w != i)
                    batch[w] = batch[i];
                w++;
            }

            uint64_t pushed = frings.produce(batch.data(), w);
            dropped_ring_full += w - pushed;
            produced += batch.size();
        }

        // ---- consume verdicts -------------------------------------------
        uint64_t n = vring.consume(vbatch.data(), vbatch.size());
        for (uint64_t i = 0; i < n; i++)
            blacklist.block(key_of(vbatch[i]), vbatch[i].until_ns);
        verdicts += n;

        // ---- bounds / pacing --------------------------------------------
        uint64_t t = now_ns();
        if (o.total_packets && produced >= o.total_packets) {
            // wait (bounded) for the consumer to drain + send verdicts
            if (drain_deadline == 0)
                drain_deadline = t + 3'000'000'000ULL;
            if (frings.total_readable() == 0 || t > drain_deadline) {
                uint64_t extra = vring.consume(vbatch.data(), vbatch.size());
                for (uint64_t i = 0; i < extra; i++)
                    blacklist.block(key_of(vbatch[i]), vbatch[i].until_ns);
                verdicts += extra;
                break;
            }
            std::this_thread::sleep_for(std::chrono::microseconds(200));
        }
        if (o.duration_s > 0 && (t - t_start) > (uint64_t)(o.duration_s * 1e9))
            break;
        if (t >= next_report) {
            std::fprintf(stderr,
                         "fsxd: produced=%" PRIu64 " verdicts=%" PRIu64
                         " vring_readable=%" PRIu64 " vring_head=%" PRIu64
                         " blacklisted=%zu suppressed=%" PRIu64 "\n",
                         produced, verdicts, vring.readable(),
                         vring.load_head(__ATOMIC_ACQUIRE),
                         blacklist.size(), suppressed);
            next_report = t + 1'000'000'000ULL;
        }
        if (frings.nearly_full(CHUNK))
            std::this_thread::sleep_for(std::chrono::microseconds(200));
    }

    // Final verdict drain on every exit path: verdicts racing the
    // shutdown still get counted (and, in --bpf mode, applied), so an
    // engine that was mid-flush when the duration expired is not lost:
    // to the last verdict the ring holds, not one 4,096-record take.
    while (uint64_t extra = vring.consume(vbatch.data(), vbatch.size())) {
        for (uint64_t i = 0; i < extra; i++)
            blacklist.block(key_of(vbatch[i]), vbatch[i].until_ns);
        verdicts += extra;
    }

    if (replay)
        std::fclose(replay);
    std::printf("{\"produced\": %" PRIu64 ", \"verdicts\": %" PRIu64
                ", \"blacklisted\": %zu, \"suppressed\": %" PRIu64
                ", \"dropped_ring_full\": %" PRIu64
                ", \"spoofed\": %" PRIu64,
                produced, verdicts, blacklist.size(), suppressed,
                dropped_ring_full, sim.spoofed());
    if (key128)
        std::printf(", \"v6_sources\": %" PRIu64 ", \"planted\": %" PRIu64,
                    sim.v6_sources(), sim.planted());
    std::printf("}\n");
    return 0;
}

}  // namespace

int main(int argc, char **argv) {
    Options o = parse(argc, argv);
    std::signal(SIGINT, on_signal);
    std::signal(SIGTERM, on_signal);

    if (o.mode == "bpf")
        return o.key_bits == 128 ? run_bpf<fsx_verdict_record6>(o)
                                 : run_bpf<fsx_verdict_record>(o);
    if (o.compact) {
        std::fprintf(stderr, "fsxd: --compact requires --bpf (the sim/"
                             "replay generators emit 48 B records)\n");
        return 2;
    }
    return o.key_bits == 128
               ? run_stream<fsx_flow_record6, fsx_verdict_record6>(o)
               : run_stream<fsx_flow_record, fsx_verdict_record>(o);
}
