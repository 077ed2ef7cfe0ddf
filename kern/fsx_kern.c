/* fsx_kern.c — the XDP fast path: parse → blacklist → rate-limit →
 * feature-extract → verdict.
 *
 * Ground-up rebuild of the reference's src/fsx_kern.c:96-347 with the
 * capabilities its README/TODO specify but never implement:
 *
 *   - runtime config map instead of compile-time thresholds
 *     (fsx_kern.c:308-310 hard-codes 1000 pps / 125 MB/s / 10 s)
 *   - all THREE rate limiters (fixed window implemented at
 *     fsx_kern.c:243-263; sliding window + token bucket specified at
 *     README.md:153-162) in integer-only arithmetic
 *   - L4 parsing (TCP/UDP/ICMP — TODO at fsx_kern.c:286-287)
 *   - per-CPU stats (the improvement proposed at fsx_kern.c:253-257;
 *     the reference's plain increments race, fsx_kern.c:210,332,342)
 *   - streaming per-flow feature extraction pushed to a ring buffer
 *     for the TPU plane (the plan that died as a comment block in
 *     src/fsx_kern_ml.c:1-17)
 *   - no printk in the hot path (the reference logs every IPv4 source,
 *     fsx_kern.c:169-175, which serializes the softirq path)
 *
 * The kernel limiter ALWAYS runs: if the TPU plane dies, this program
 * alone is the reference's full CPU data plane (fail-open design,
 * SURVEY.md §5.3).  The TPU plane adds ML verdicts by writing into
 * blacklist_map through the daemon.
 *
 * In-kernel ML (the reference's fsx_kern_ml.c ambition) ships in the
 * ASSEMBLER twin only: bpf/progs.py build(ml=True) adds fn_ml_score —
 * a distilled int8 classifier (struct fsx_ml_model in fsx_schema.h,
 * hot-swapped via ml_model_map by `fsx distill --pin`) banding each
 * would-be-emitted record into drop/pass/escalate (docs/DISTILL.md).
 * This C twin stays the pre-ML reference implementation; its behavior
 * is identical to an --ml image with no model pushed (valid == 0).
 *
 * Verifier discipline (fsx_kern_ml.c:1-17 constraints): every map
 * lookup NULL-checked, no unbounded loops, no floats (token bucket
 * uses milli-tokens), stack < 512 B.
 */
#include <linux/bpf.h>
#include <bpf/bpf_helpers.h>

#include "fsx_schema.h"
#include "fsx_compute.h"
#include "parsing.h"

char LICENSE[] SEC("license") = "GPL";

/* ---- maps: the kernel/user seam (successor of fsx_kern.c:56-94) ---- */

struct {
	__uint(type, BPF_MAP_TYPE_ARRAY);
	__uint(max_entries, 1);
	__type(key, __u32);
	__type(value, struct fsx_config);
} config_map SEC(".maps");

/* Stateless firewall rules (the reference's planned "basic firewall"
 * with config-file drop rules, README.md:70-74): key packs
 * (l4_proto << 16) | dport in host order (0 = wildcard in either
 * position), value = FSX_RULE_* action.  Pushed by user space
 * (fsxd --rule / FsxConfig.rules); the per-packet lookups are gated on
 * cfg->rule_count so rule-less deployments pay nothing. */
struct {
	__uint(type, BPF_MAP_TYPE_HASH);
	__uint(max_entries, FSX_MAX_RULES);
	__type(key, __u32);
	__type(value, __u64);
} rule_map SEC(".maps");

/* Blacklist: key = folded source addr, value = blocked-until (ktime ns).
 * Serves v4 exactly and v6 approximately via the 32-bit fold; written by
 * this program (v4 rate limit) AND by the daemon (TPU verdict ingress:
 * every verdict of a table keyed by 32 bits, TableConfig.key_bits 32,
 * whose data plane keys a v6 source on the fold; only the IPv4 sources'
 * of one keyed by 128, fsxd --key-bits 128) — the north star's plugin
 * seam. */
struct {
	__uint(type, BPF_MAP_TYPE_LRU_HASH);
	__uint(max_entries, FSX_MAX_TRACK_IPS);
	__type(key, __u32);
	__type(value, __u64);
} blacklist_map SEC(".maps");

/* EXACT IPv6 blacklist: key = full 128-bit source (reference parity:
 * src/fsx_struct.h:9 __u128 + blacklist_v6, src/fsx_kern.c:66-72,
 * 159-176).  The kernel rate limiter and `fsx block <v6addr>` write
 * HERE for v6 sources, so a block can never hit an innocent source
 * that merely shares a 32-bit fold with an attacker.  So do the TPU
 * plane's verdicts on v6 sources where its table keys by 128 bits
 * (fsxd --bpf --key-bits 128 writes them here, whole).  The folded map
 * is still consulted for v6: it carries the ML verdicts of a table
 * keyed by 32 bits (TableConfig.key_bits 32), which live in the folded
 * key space — approximate, and documented as such in bpf/blacklist.py. */
struct fsx_v6key {
	__u32 addr[4];
};

struct {
	__uint(type, BPF_MAP_TYPE_LRU_HASH);
	__uint(max_entries, FSX_MAX_TRACK_IPS);
	__type(key, struct fsx_v6key);
	__type(value, __u64);
} blacklist_v6 SEC(".maps");

/* Per-source-IP limiter state (successor of ip_stats_map, fsx_kern.c:88-94). */
struct {
	__uint(type, BPF_MAP_TYPE_LRU_HASH);
	__uint(max_entries, FSX_MAX_TRACK_IPS);
	__type(key, __u32);
	__type(value, struct fsx_ip_state);
} ip_state_map SEC(".maps");

/* Per-flow streaming feature stats, keyed by (saddr^dport fold). */
struct {
	__uint(type, BPF_MAP_TYPE_LRU_HASH);
	__uint(max_entries, FSX_MAX_TRACK_IPS);
	__type(key, __u32);
	__type(value, struct fsx_flow_stats);
} flow_stats_map SEC(".maps");

/* Global counters, per-CPU: race-free increments, user space aggregates. */
struct {
	__uint(type, BPF_MAP_TYPE_PERCPU_ARRAY);
	__uint(max_entries, 1);
	__type(key, __u32);
	__type(value, struct fsx_stats);
} stats_map SEC(".maps");

/* Feature egress ring: drained by the C++ daemon, scored on TPU. */
struct {
	__uint(type, BPF_MAP_TYPE_RINGBUF);
	__uint(max_entries, FSX_RING_SIZE);
} feature_ring SEC(".maps");

/* ---- feature extraction (streaming estimators for model.py:117;
 * limiters + integer helpers live in fsx_compute.h, shared with the
 * userspace test harness) ---- */

/* Update per-flow stats and emit a feature record if the flow is due.
 * Feature semantics mirror the trainer exactly (train/serve skew fix):
 *   mean = sum/n ; var = sumsq/n - mean^2 ; std = sqrt(var)
 * IATs are emitted in MICROSECONDS (CICIDS2017 convention). */
static __always_inline void extract_features(
	struct fsx_pkt *pkt, __u64 now, __u64 bytes)
{
	__u32 fkey = pkt->saddr ^ ((__u32)pkt->dport << 16);
	struct fsx_flow_stats *fs, zero = {};
#ifndef FSX_EMIT_COMPACT
	struct fsx_flow_record *rec;
#endif

	fs = bpf_map_lookup_elem(&flow_stats_map, &fkey);
	if (!fs) {
		zero.first_ts_ns = now;
		zero.dst_port = fsx_htons(pkt->dport);
		bpf_map_update_elem(&flow_stats_map, &fkey, &zero, BPF_ANY);
		fs = bpf_map_lookup_elem(&flow_stats_map, &fkey);
		if (!fs)
			return;
	}

	/* Same flow key can run on several CPUs at once (same saddr/dport,
	 * different sport → different RSS queues), so counter updates are
	 * atomic, mirroring fsx_compute.h's limiter counters.  The
	 * now > last_ts_ns guard rejects the cross-CPU ordering race where
	 * another CPU committed a NEWER last_ts first — an unguarded
	 * subtraction would wrap to ~2^64 and poison the IAT features. */
	if (fs->pkt_count > 0 && now > fs->last_ts_ns) {
		__u64 iat = now - fs->last_ts_ns;
		/* clamp to 2^21 µs (~35 min) before squaring: square 2^42
		 * leaves 2^22 worst-case additions of headroom in the u64
		 * accumulator (centuries per flow) — saturating only the
		 * single multiply would let the SUM wrap after two
		 * near-maximal gaps */
		__u64 iat_us = iat / 1000;

		if (iat_us > (1ULL << 21))
			iat_us = 1ULL << 21;
		fsx_atomic_add(&fs->iat_sum_ns, iat);
		fsx_atomic_add(&fs->iat_sq_sum_us2, iat_us * iat_us);
		if (iat > fs->iat_max_ns)
			fs->iat_max_ns = iat;  /* benign race: a lost max */
	}
	__u64 n_now = fsx_atomic_add(&fs->pkt_count, 1) + 1;
	fsx_atomic_add(&fs->byte_sum, bytes);
	fsx_atomic_add(&fs->byte_sq_sum, bytes * bytes);
	fs->last_ts_ns = now;

	/* Emit every packet while the flow is young, then every 16th:
	 * bounds ring bandwidth at line rate without starving the model. */
	if (n_now > 16 && (n_now & 15))
		return;

	/* All-integer feature derivation (no FPU in eBPF,
	 * fsx_kern_ml.c:3-6), SHARED by both emit formats below — one
	 * copy, so the wire formats can never skew against each other.
	 * Values beyond u32 saturate at the emit sites — the model's
	 * input quantization clips far below 2^32 anyway. */
	{
		__u64 n = fs->pkt_count;
		__u64 mean = fs->byte_sum / n;
		__u64 var = fs->byte_sq_sum / n > mean * mean
			? fs->byte_sq_sum / n - mean * mean : 0;
		/* flow-age features (slots 3/4, schema.FEATURE_NAMES): the
		 * slow-attack separators the original variance/avg-size
		 * slots (redundant with std/mean) couldn't provide.
		 * pps_x1000 = n * 1e9 / dur_us (n*1e9 is overflow-free for
		 * any realistic count; dur_us == 0 -> 0, rate unknown). */
		__u64 dur_ns = fs->last_ts_ns - fs->first_ts_ns;
		__u64 dur_us = dur_ns / 1000;
		__u64 dur_ms = dur_ns / 1000000;
		__u64 pps_x1000 = dur_us ? (n * 1000000000ULL) / dur_us : 0;
		__u64 iat_n = n > 1 ? n - 1 : 1;
		__u64 iat_mean_us = (fs->iat_sum_ns / iat_n) / 1000;
		__u64 iat_mean_sq = iat_mean_us * iat_mean_us;
		__u64 iat_var = fs->iat_sq_sum_us2 / iat_n > iat_mean_sq
			? fs->iat_sq_sum_us2 / iat_n - iat_mean_sq : 0;
		__u64 iat_max_us = fs->iat_max_ns / 1000;
		__u8 fl = (pkt->is_ipv6 ? FSX_FLAG_IPV6 : 0)
			| (pkt->l4_proto == IPPROTO_TCP ? FSX_FLAG_TCP : 0)
			| (pkt->l4_proto == IPPROTO_UDP ? FSX_FLAG_UDP : 0)
			| (pkt->l4_proto == IPPROTO_ICMP
			   || pkt->l4_proto == IPPROTO_ICMPV6 ? FSX_FLAG_ICMP : 0)
			| ((pkt->tcp_flags & FSX_TCP_SYN) ? FSX_FLAG_TCP_SYN : 0);

#ifdef FSX_EMIT_COMPACT
		/* Compact 16 B records: features quantized IN KERNEL to the
		 * u8 e5m3 minifloat the host decoder shares (fsx_compute.h
		 * fsx_minifloat8 == schema.quantize_feat_minifloat, lockstep-
		 * tested) — 3x less ring + host->device traffic, zero host-
		 * side quantization work.  Layout: struct fsx_compact_record
		 * (fsx_schema.h).  Saturate to u32 BEFORE quantizing, exactly
		 * like the 48 B path's feat[] fields. */
		struct fsx_compact_record *crec;
		__u32 len8 = (__u32)((bytes + 4) >> 3);

		crec = bpf_ringbuf_reserve(&feature_ring, sizeof(*crec), 0);
		if (!crec)
			return; /* ring full: TPU plane lags; fail open */
		crec->w0_saddr = pkt->saddr;
		crec->w1_feat_lo = fsx_minifloat8(fs->dst_port)
			| fsx_minifloat8(fsx_sat_u32(mean)) << 8
			| fsx_minifloat8(fsx_isqrt_u64(var)) << 16
			| fsx_minifloat8(fsx_sat_u32(dur_ms)) << 24;
		crec->w2_feat_hi = fsx_minifloat8(fsx_sat_u32(pps_x1000))
			| fsx_minifloat8(fsx_sat_u32(iat_mean_us)) << 8
			| fsx_minifloat8(fsx_isqrt_u64(iat_var)) << 16
			| fsx_minifloat8(fsx_sat_u32(iat_max_us)) << 24;
		crec->w3_len_flags_ts = (len8 > 2047 ? 2047 : len8)
			| ((__u32)fl & 0x1F) << 11
			| (__u32)((now / 1000) & 0xFFFF) << 16;
		bpf_ringbuf_submit(crec, 0);
#else
		rec = bpf_ringbuf_reserve(&feature_ring, sizeof(*rec), 0);
		if (!rec)
			return;  /* ring full: TPU plane lags; fail open */
		rec->ts_ns = now;
		rec->saddr = pkt->saddr;
		rec->pkt_len = (__u16)bytes;
		rec->ip_proto = pkt->l4_proto;
		rec->flags = fl;
		rec->feat[0] = fs->dst_port;
		rec->feat[1] = fsx_sat_u32(mean);
		rec->feat[2] = fsx_isqrt_u64(var);
		rec->feat[3] = fsx_sat_u32(dur_ms);
		rec->feat[4] = fsx_sat_u32(pps_x1000);
		rec->feat[5] = fsx_sat_u32(iat_mean_us);
		rec->feat[6] = fsx_isqrt_u64(iat_var);
		rec->feat[7] = fsx_sat_u32(iat_max_us);
		bpf_ringbuf_submit(rec, 0);
#endif
	}
}

/* ---- the XDP program (successor of fsx(), fsx_kern.c:97-347) ---- */

SEC("xdp")
int fsx(struct xdp_md *ctx)
{
	void *data = (void *)(long)ctx->data;
	void *data_end = (void *)(long)ctx->data_end;
	__u64 now = bpf_ktime_get_ns();
	__u64 bytes = (char *)data_end - (char *)data;
	struct fsx_pkt pkt = {};
	struct fsx_stats *stats;
	struct fsx_config *cfg;
	__u32 zero_key = 0;
	int rc, over;

	stats = bpf_map_lookup_elem(&stats_map, &zero_key);
	cfg = bpf_map_lookup_elem(&config_map, &zero_key);
	if (!stats || !cfg)
		return XDP_PASS;    /* verifier-mandated NULL checks */
	/* ARRAY map lookups never return NULL — they return the pre-zeroed
	 * element.  An all-zero config would make every limiter fire on the
	 * first packet (fail CLOSED).  The explicit valid flag (set by
	 * pack_kernel_config) is the "daemon has pushed a config" marker:
	 * until then, pass everything (fail open).  A dedicated flag rather
	 * than overloading window_ns, which is legitimately 0 for a
	 * token-bucket config. */
	if (!cfg->valid)
		return XDP_PASS;

	rc = fsx_parse_packet(data, data_end, &pkt);
	if (rc < 0)
		return XDP_DROP;    /* malformed (fsx_kern.c:126) */
	if (rc > 0)
		return XDP_PASS;    /* non-IP (fsx_kern.c:130) */

	/* 0. stateless firewall rules (planned "basic firewall",
	 * reference README.md:70-74): exact (proto, dport), then
	 * (proto, any-port), then (any-proto, dport).  Before any per-IP
	 * state is touched — a dropped-by-rule packet must not feed the
	 * limiter windows or the feature stream. */
	if (cfg->rule_count) {
		__u16 dport_h = fsx_htons(pkt.dport);
		__u32 rk = ((__u32)pkt.l4_proto << 16) | dport_h;
		__u64 *act = bpf_map_lookup_elem(&rule_map, &rk);

		if (!act) {
			rk = (__u32)pkt.l4_proto << 16;
			act = bpf_map_lookup_elem(&rule_map, &rk);
		}
		if (!act) {
			rk = dport_h;
			act = bpf_map_lookup_elem(&rule_map, &rk);
		}
		if (act && *act == FSX_RULE_DROP) {
			stats->dropped_rule++;
			return XDP_DROP;
		}
	}

	/* 1. blacklist gate with TTL expiry (fsx_kern.c:189-216).
	 * v6 checks the EXACT 128-bit map first (fsx_kern.c:159-166
	 * parity), then both fall through to the folded map (ML-verdict
	 * ingress from the TPU plane). */
	if (pkt.is_ipv6) {
		__u64 *until = bpf_map_lookup_elem(&blacklist_v6,
						   pkt.saddr6);

		if (until) {
			if (now < *until) {
				stats->dropped_blacklist++;
				return XDP_DROP;
			}
			bpf_map_delete_elem(&blacklist_v6, pkt.saddr6);
		}
	}
	{
		__u64 *until = bpf_map_lookup_elem(&blacklist_map, &pkt.saddr);

		if (until) {
			if (now < *until) {
				stats->dropped_blacklist++;
				return XDP_DROP;
			}
			bpf_map_delete_elem(&blacklist_map, &pkt.saddr);
		}
	}

	/* 2. per-IP rate limit (fsx_kern.c:222-312) */
	{
		struct fsx_ip_state *st, zero = {};

		st = bpf_map_lookup_elem(&ip_state_map, &pkt.saddr);
		if (!st) {
			zero.win_start_ns = now;
			bpf_map_update_elem(&ip_state_map, &pkt.saddr, &zero,
					    BPF_ANY);
			st = bpf_map_lookup_elem(&ip_state_map, &pkt.saddr);
			if (!st)
				goto features;   /* table churn: fail open */
		}

		switch (cfg->limiter_kind) {
		case FSX_LIMITER_SLIDING_WINDOW:
			over = fsx_limiter_sliding_window(cfg, st, now, bytes);
			break;
		case FSX_LIMITER_TOKEN_BUCKET:
			over = fsx_limiter_token_bucket(cfg, st, now, bytes);
			break;
		default:
			over = fsx_limiter_fixed_window(cfg, st, now, bytes);
		}

		if (over) {
			__u64 until = now + cfg->block_ns;

			/* fsx_kern.c:317-325: insert + drop this packet.
			 * v6 sources go in the EXACT map (the full source
			 * is in hand right now), matching the reference's
			 * blacklist_v6 insert — never the fold, which
			 * could block an innocent colliding source. */
			if (pkt.is_ipv6)
				bpf_map_update_elem(&blacklist_v6,
						    pkt.saddr6, &until,
						    BPF_ANY);
			else
				bpf_map_update_elem(&blacklist_map,
						    &pkt.saddr, &until,
						    BPF_ANY);
			stats->dropped_rate++;
			return XDP_DROP;
		}
	}

features:
	/* 3. streaming features → ring (the fsx_kern_ml.c plan, real) */
	extract_features(&pkt, now, bytes);

	stats->allowed++;
	return XDP_PASS;
}
