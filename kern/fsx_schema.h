/* fsx_schema.h — GENERATED by flowsentryx_tpu.core.codegen. DO NOT EDIT.
 *
 * Shared kernel/user data layout, successor of the reference's
 * hand-written src/fsx_struct.h.  Regenerate with:
 *     python -m flowsentryx_tpu.core.codegen
 */
#ifndef FSX_SCHEMA_H
#define FSX_SCHEMA_H

#if defined(__bpf__) || defined(__linux__)
#include <linux/types.h>
#else
#include <stdint.h>
typedef uint64_t __u64;
typedef uint32_t __u32;
typedef uint16_t __u16;
typedef uint8_t __u8;
#endif

/* ---- sizing ---- */
#define FSX_NUM_FEATURES 8
#define FSX_MAX_TRACK_IPS 1048576  /* = TableConfig.capacity default; supersedes fsx_struct.h:7 (100000) */
#define FSX_RING_SIZE (1 << 22)    /* feature ringbuf bytes, per CPU */

/* ---- record flags ---- */
#define FSX_FLAG_IPV6 1
#define FSX_FLAG_TCP_SYN 2
#define FSX_FLAG_TCP 4
#define FSX_FLAG_UDP 8
#define FSX_FLAG_ICMP 16

/* ---- verdict codes (drop-cause attribution) ---- */
#define FSX_VERDICT_PASS 0
#define FSX_VERDICT_DROP_BLACKLIST 1
#define FSX_VERDICT_DROP_RATE 2
#define FSX_VERDICT_DROP_ML 3

/* ---- kernel-distilled classifier bands (fsx distill; two-tier escalation) ---- */
#define FSX_ML_BOUNDS_PER_FEATURE 255
#define FSX_ML_BAND_PASS 0
#define FSX_ML_BAND_ESCALATE 1
#define FSX_ML_BAND_DROP 2
#define FSX_ML_BAND_DISABLED 3

/* ---- limiter kinds (config map) ---- */
#define FSX_LIMITER_FIXED_WINDOW 0
#define FSX_LIMITER_SLIDING_WINDOW 1
#define FSX_LIMITER_TOKEN_BUCKET 2

/* Stateless firewall rules (reference README.md:70-74 planned firewall): */
/* rule_map key = (l4_proto << 16) | dport (host order, 0 = wildcard) */
#define FSX_MAX_RULES 1024
#define FSX_RULE_DROP 1

/* One feature-ring entry: kernel -> user wire format.
 * GENERATED from flowsentryx_tpu.core.schema.FLOW_RECORD_DTYPE (packed, 48 bytes). */
struct fsx_flow_record {
	__u64 ts_ns; /* bpf_ktime_get_ns() at packet arrival */
	__u32 saddr; /* IPv4 source, or 32-bit fold of IPv6 */
	__u16 pkt_len; /* wire length of this packet */
	__u8 ip_proto; /* IPPROTO_* */
	__u8 flags; /* FSX_FLAG_* bits */
	__u32 feat[8]; /* integer feature estimates (no FPU in eBPF; host casts to f32) */
} __attribute__((packed));

/* One feature-ring entry: the source keyed by all 128 bits (TableConfig.key_bits 128).
 * GENERATED from flowsentryx_tpu.core.schema.FLOW_RECORD6_DTYPE (packed, 64 bytes). */
struct fsx_flow_record6 {
	__u64 ts_ns; /* bpf_ktime_get_ns() at packet arrival */
	__u32 saddr6[4]; /* full source, wire words; IPv4 as ::ffff:a.b.c.d */
	__u16 pkt_len; /* wire length of this packet */
	__u8 ip_proto; /* IPPROTO_* */
	__u8 flags; /* FSX_FLAG_* bits */
	__u32 feat[8]; /* integer feature estimates (no FPU in eBPF; host casts to f32) */
	__u32 _pad; /* to 64 B */
} __attribute__((packed));

/* One COMPACT feature-ring entry (16 B): the kernel-quantized twin of
 * fsx_flow_record for deployments built with FSX_EMIT_COMPACT — 3x
 * less ring/wire traffic.  GENERATED from the layout in
 * flowsentryx_tpu.core.schema (encode_compact):
 *   w0  folded source address
 *   w1  feat[0..3] as u8 e5m3 minifloat (fsx_minifloat8)
 *   w2  feat[4..7] likewise
 *   w3  bits 0-10  pkt_len in 8-byte units (round-to-nearest, sat)
 *       bits 11-15 FSX_FLAG_* bits
 *       bits 16-31 (ktime_ns/1000) & 0xFFFF — the host unwraps this
 *                  against its own MONOTONIC clock; valid while drain
 *                  latency stays under 65 ms (ring sizing enforces) */
struct fsx_compact_record {
	__u32 w0_saddr;
	__u32 w1_feat_lo;
	__u32 w2_feat_hi;
	__u32 w3_len_flags_ts;
} __attribute__((packed));

/* Streaming per-flow statistics kept in the kernel to derive the
 * 8 model features online (train/serve skew fix; the reference's
 * in-kernel plan stopped at a comment block, fsx_kern_ml.c:1-17).
 * GENERATED from schema.FLOW_STATS_FIELDS.
 */
struct fsx_flow_stats {
	__u64 pkt_count;
	__u64 byte_sum;
	__u64 byte_sq_sum;
	__u64 first_ts_ns;
	__u64 last_ts_ns;
	__u64 iat_sum_ns;
	__u64 iat_sq_sum_us2;
	__u64 iat_max_ns;
	__u16 dst_port;
} __attribute__((packed));

/* Runtime policy, pushed by user space into the config array map
 * (index 0).  Replaces the reference's compile-time constants
 * (fsx_kern.c:308-310).  GENERATED from
 * flowsentryx_tpu.core.config.FsxConfig.KERNEL_CONFIG_FIELDS. */
struct fsx_config {
	__u32 limiter_kind; /* FSX_LIMITER_* */
	__u32 valid; /* nonzero once a config has been pushed; the all-zero ARRAY-map default means "no config yet" (fail open) */
	__u64 pps_threshold; /* packets per window */
	__u64 bps_threshold; /* bytes per window */
	__u64 window_ns;
	__u64 block_ns; /* blacklist TTL */
	__u64 bucket_rate_pps; /* token refill rate (packets/s) */
	__u64 bucket_burst; /* token bucket depth (packets) */
	__u64 bucket_rate_bps; /* byte-bucket refill rate (bytes/s); 0 with 0 depth = byte dimension off */
	__u64 bucket_burst_bytes; /* byte bucket depth (bytes) */
	__u64 rule_count; /* number of stateless firewall rules pushed into rule_map; 0 skips the rule lookups entirely */
	__u64 hash_salt; /* salt for user-plane slot/owner hashing (low 32 bits used).  No kernel-side consumer exists: BPF maps hash internally with their own seed.  Carried in the blob so a deployment that FIXES the salt in its config file presents one value to both planes; a serve-drawn random salt is user-plane only */
} __attribute__((packed));

/* Per-source-IP fast-path counters (successor of struct ip_stats,
 * fsx_struct.h:17-22, extended with sliding-window + token-bucket
 * state, README.md:153-162).  tokens_milli = tokens * 1000
 * (no floats in eBPF).  GENERATED from schema.IP_STATE_FIELDS.
 */
struct fsx_ip_state {
	__u64 win_start_ns;
	__u64 win_pps;
	__u64 win_bps;
	__u64 prev_pps;
	__u64 prev_bps;
	__u64 tokens_milli;
	__u64 tok_ts_ns;
	__u64 tok_bytes;
} __attribute__((packed));

/* ---- shared-memory rings (daemon <-> engine transport) ----
 * SPSC ring in a file-backed mmap: this header, then `capacity`
 * fixed-size records.  head/tail are monotonic record counts (mod
 * capacity for the slot), each on its own cache line.  GENERATED from
 * schema.SHM_* constants. */
#define FSX_SHM_MAGIC 0x46535852494E4731ULL
struct fsx_shm_ring_hdr {
	__u64 magic;
	__u64 capacity;      /* record slots, power of two */
	__u64 record_size;   /* bytes per record */
	__u64 _meta_pad[5];
	__u64 head;          /* producer cursor (total records written) */
	__u64 _head_pad[7];
	__u64 tail;          /* consumer cursor (total records read) */
	__u64 _tail_pad[7];
} __attribute__((packed));

/* One verdict-ring entry (engine -> daemon): newly blacklisted source.
 * GENERATED from schema.VERDICT_RECORD_DTYPE (16 bytes). */
struct fsx_verdict_record {
	__u32 saddr;         /* folded source address */
	__u32 _pad;
	__u64 until_ns;      /* blacklist expiry, kernel clock ns */
} __attribute__((packed));

/* The verdict-ring entry of a 128-bit-keyed deployment: the full
 * source.  GENERATED from schema.VERDICT_RECORD6_DTYPE (32 bytes). */
struct fsx_verdict_record6 {
	__u32 saddr6[4];     /* full source, wire words; IPv4 as ::ffff:a.b.c.d */
	__u64 until_ns;      /* blacklist expiry, kernel clock ns */
	__u64 _pad;
} __attribute__((packed));

/* Global counters (successor of struct stats, fsx_struct.h:11-15),
 * kept in a PER_CPU array so increments are race-free; user space
 * aggregates (the improvement proposed at fsx_kern.c:253-257).
 * ml_pass/ml_escalated are the kernel-tier classifier's band
 * counters (dropped_ml carries its drop band).
 * GENERATED from schema.KERNEL_STATS_FIELDS.
 */
struct fsx_stats {
	__u64 allowed;
	__u64 dropped_blacklist;
	__u64 dropped_rate;
	__u64 dropped_ml;
	__u64 dropped_rule;
	__u64 ml_pass;
	__u64 ml_escalated;
} __attribute__((packed));

/* Kernel-distilled int8 classifier (flowsentryx_tpu/distill/): the
 * hot-swappable value of ml_model_map.  fn_ml_score (bpf/progs.py,
 * ml=True images) computes q_i = qbase[i] + |{r : x_i > bounds_m1[i][r]}|
 * and s = sum w[i]*q[i], then bands s against acc_drop/acc_pass (s64,
 * input zero-point pre-folded by the distiller).  Boundaries are exact
 * u32 images of the engine's f32 input observer, so the kernel verdict
 * is bit-exact with the TPU int8 lane.  GENERATED from
 * flowsentryx_tpu.core.schema.ML_MODEL_* offsets. */
struct fsx_ml_model {
	__u32 valid;         /* nonzero once a model is pushed; 0 = stage off */
	__u32 _reserved;
	__u64 acc_drop;      /* s64: s >= acc_drop -> blacklist + drop */
	__u64 acc_pass;      /* s64: s <= acc_pass -> pass, emit suppressed */
	__u32 w[8];          /* s32 weights (int8-valued, two's complement) */
	__u32 qbase[8];      /* q_i(0) */
	__u32 bounds_m1[2040]; /* per-feature sorted boundaries - 1, 0xFFFFFFFF pad */
} __attribute__((packed));

#endif /* FSX_SCHEMA_H */
