"""Analytic per-dispatch cost model: FLOPs and HBM bytes from arch shapes.

The performance-attribution plane's arithmetic core. Built ONCE at model
load (models/loader.py attaches it to the arch config), it prices every
runner dispatch in model FLOPs and HBM bytes so the engine can report
MFU / MBU / roofline placement from its own counters instead of a
bench-side ``2 * params * tok/s`` guess that ignores attention and KV
traffic entirely.

Accounting conventions (documented in the README assumptions table;
tests/metrics/test_costmodel.py pins them with hand-computed counts):

* **FLOPs are useful model FLOPs** — one multiply-add = 2 FLOPs over
  the real (unpadded) tokens of a wave. Bucket padding, replicated
  TPLA rope-score work and KV-head replicas burn real device cycles
  but count toward the denominator (device time), not the numerator —
  exactly what MFU is supposed to expose.
* **Weights stream once per dispatch** — each forward pass reads every
  resident dense weight once regardless of batch width (the decode
  regime this plane exists for); MoE layers read only the routed
  experts, ``min(tokens * top_k, num_experts)`` per layer.
* **KV bytes are storage bytes** — per-token-position row cost comes
  from the model's own ``kv_cache_page_bytes`` (so fp8 caches, TPU
  lane padding, KV-head replicas and the TPLA per-rank latent slice
  are priced exactly as stored); TPLA multiplies the per-rank row by
  the shard count (each rank reads its disjoint slice plus its own
  rope-sidecar copy). SSM state rows ride the same kv_read/kv_write
  kinds (they are the recurrence's KV analogue).
* **Attention pairs** — the runner sums, over each scheduled token,
  the KV length it attends (``kv_terms``); causal prefill therefore
  charges ``ctx*n + n(n+1)/2`` pairs per request chunk and decode
  ``ctx+1``. A uniform sliding window clamps the span.
* **Peaks are fleet peaks** — per-chip public-spec numbers times the
  mesh's device count; non-TPU backends get
  a nominal host peak so CPU-smoke MFU/MBU stay comparable
  run-to-run (they are not absolute utilization there).
"""

import math
from dataclasses import dataclass, field
from typing import Any, Optional

from vllm_distributed_tpu.logger import init_logger

logger = init_logger(__name__)

# Peak dense-matmul FLOP/s per chip (public specs, bf16).
PEAK_FLOPS_PER_CHIP = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}

# Peak HBM bandwidth per chip (public specs, bytes/s) — the decode
# roofline (decode is weight/KV-bandwidth-bound, not FLOP-bound).
PEAK_HBM_PER_CHIP = {
    "v4": 1228e9,
    "v5e": 819e9,
    "v5p": 2765e9,
    "v6e": 1638e9,
}

# device_kind spellings that do not literally contain the generation
# key ("TPU v5 lite" is a v5e).
_KIND_ALIASES = (("v5 lite", "v5e"), ("v5lite", "v5e"), ("v6 lite", "v6e"))

# Nominal peaks for non-TPU backends (CPU smoke): MFU/MBU become
# machine-relative trend numbers, not absolute utilization.
HOST_PEAK_FLOPS = 1e12
HOST_PEAK_HBM = 100e9

# vdt:roofline_bound{phase} gauge encoding (rendered + README-documented).
ROOFLINE_CODES = {"host": 0, "bandwidth": 1, "compute": 2}


def peak_flops_per_chip(device_kind: str) -> float:
    return _lookup_peak(PEAK_FLOPS_PER_CHIP, device_kind)


def peak_hbm_per_chip(device_kind: str) -> float:
    return _lookup_peak(PEAK_HBM_PER_CHIP, device_kind)


def _lookup_peak(table: dict, device_kind: str) -> float:
    kind = (device_kind or "").lower()
    for alias, gen in _KIND_ALIASES:
        if alias in kind:
            return table[gen]
    for gen, peak in table.items():
        if gen in kind:
            return peak
    if "cpu" in kind or "host" in kind or not kind:
        return (HOST_PEAK_FLOPS if table is PEAK_FLOPS_PER_CHIP
                else HOST_PEAK_HBM)
    # A utilization against another chip's peak is a wrong number, not
    # an approximate one: an accelerator missing from the tables is an
    # error to fix here.
    raise ValueError(
        f"no published peaks for accelerator kind {device_kind!r}; add "
        f"it to PEAK_FLOPS_PER_CHIP / PEAK_HBM_PER_CHIP")


@dataclass(frozen=True)
class WaveCost:
    """Price of one dispatched wave (or one fused multi-step burst)."""
    flops: float = 0.0
    weight_bytes: float = 0.0
    kv_read_bytes: float = 0.0
    kv_write_bytes: float = 0.0
    act_bytes: float = 0.0

    @property
    def total_bytes(self) -> float:
        return (self.weight_bytes + self.kv_read_bytes +
                self.kv_write_bytes + self.act_bytes)


def classify_roofline(phase_entry: dict, peaks: dict,
                      host_factor: float = 1.0) -> str:
    """Place one phase's accumulated (device_seconds, host_seconds,
    flops, bytes) on the roofline: "host" when the host-side share of
    the phase's wall time exceeds the device share (the device is
    starved, not saturated), else "compute" vs "bandwidth" by which
    peak fraction the measured device time is closer to."""
    dev_s = float(phase_entry.get("device_seconds", 0.0))
    if dev_s <= 0.0:
        return "host"
    if float(phase_entry.get("host_seconds", 0.0)) > host_factor * dev_s:
        return "host"
    pf = float(peaks.get("flops", 0.0)) or HOST_PEAK_FLOPS
    pb = float(peaks.get("hbm", 0.0)) or HOST_PEAK_HBM
    flops_frac = float(phase_entry.get("flops", 0.0)) / (dev_s * pf)
    bw_frac = float(phase_entry.get("bytes", 0.0)) / (dev_s * pb)
    return "compute" if flops_frac >= bw_frac else "bandwidth"


@dataclass
class CostModel:
    """Per-dispatch analytic cost constants for one loaded model.

    All per-token constants are whole-model (summed over layers and,
    under TP, over ranks where work is disjoint — sharded matmuls count
    once, which is also what "useful FLOPs" means)."""

    # -- FLOPs ----------------------------------------------------------
    # Projections + MLP/MoE/SSM per token through the whole stack
    # (everything except attention pairs and the LM head).
    linear_flops_per_token: float = 0.0
    # Attention FLOPs per (query token, attended KV position) pair,
    # summed over attention layers: scores + PV.
    attn_flops_per_token_kv: float = 0.0
    # LM-head matmul per sampled row.
    lm_head_flops_per_row: float = 0.0
    # -- HBM bytes ------------------------------------------------------
    # Dense weights (incl. LM head + embeddings) streamed once per
    # forward pass.
    dense_weight_bytes: float = 0.0
    # MoE: bytes of ONE expert's FFN weights at ONE layer, and the
    # routing width, for the min(tokens*topk, E) per-layer read.
    moe_layers: int = 0
    num_experts: int = 0
    experts_per_token: int = 0
    expert_bytes: float = 0.0
    # Paged-KV row cost per token position (all layers, storage bytes;
    # 0 for pure-SSM stacks).
    kv_row_read_bytes: float = 0.0
    kv_row_write_bytes: float = 0.0
    # SSM recurrence state read+write per token (0 for pure attention).
    state_read_bytes_per_token: float = 0.0
    state_write_bytes_per_token: float = 0.0
    # Residual-stream traffic per token + materialized logits per row.
    act_bytes_per_token: float = 0.0
    logits_bytes_per_row: float = 0.0
    # Uniform sliding window (tokens) clamping the attention span, if
    # every layer is windowed; None = full causal.
    attn_window: Optional[int] = None
    # -- peaks ----------------------------------------------------------
    num_chips: int = 1
    peak_flops: float = HOST_PEAK_FLOPS
    peak_hbm: float = HOST_PEAK_HBM
    # Assumption echo for /debug/perf + README cross-checks.
    meta: dict = field(default_factory=dict)

    # ------------------------------------------------------------------
    def wave_cost(self, q_tokens: int, kv_terms: float,
                  sampled_rows: int, passes: int = 1) -> WaveCost:
        """Price one dispatch: ``q_tokens`` scheduled (real) tokens
        attending ``kv_terms`` total KV positions, sampling
        ``sampled_rows`` logits rows, across ``passes`` forward passes
        (1 for a normal wave; the fused multi-step burst streams the
        weights once per in-graph step)."""
        flops = (q_tokens * self.linear_flops_per_token +
                 kv_terms * self.attn_flops_per_token_kv +
                 sampled_rows * self.lm_head_flops_per_row)
        weights = passes * self.dense_weight_bytes
        if self.moe_layers and passes:
            per_pass = max(q_tokens // passes, 1)
            weights += (passes * self.moe_layers *
                        min(per_pass * self.experts_per_token,
                            self.num_experts) * self.expert_bytes)
        kv_read = (kv_terms * self.kv_row_read_bytes +
                   q_tokens * self.state_read_bytes_per_token)
        kv_write = (q_tokens * self.kv_row_write_bytes +
                    q_tokens * self.state_write_bytes_per_token)
        act = (q_tokens * self.act_bytes_per_token +
               sampled_rows * self.logits_bytes_per_row)
        return WaveCost(flops=flops, weight_bytes=weights,
                        kv_read_bytes=kv_read, kv_write_bytes=kv_write,
                        act_bytes=act)

    def clamp_span(self, kv_len: float) -> float:
        """Attention span for one token at KV length ``kv_len`` under
        the model's uniform window (identity when full-causal)."""
        if self.attn_window is not None:
            return min(kv_len, float(self.attn_window))
        return kv_len

    def span_sum(self, ctx: float, n: int) -> float:
        """Total attended KV positions for ``n`` consecutive tokens
        starting at context ``ctx`` (token j attends ctx+j positions,
        window-clamped) — closed form, O(1) regardless of chunk width
        (this runs per request per dispatch on the engine-core
        thread)."""
        if self.attn_window is None:
            return n * ctx + n * (n + 1) / 2
        w = float(self.attn_window)
        # First k tokens still fit under the window, the rest saturate.
        k = max(0.0, min(float(n), w - ctx))
        return k * ctx + k * (k + 1) / 2 + (n - k) * w

    # -- bench helpers --------------------------------------------------
    def decode_flops_per_token(self, ctx: float) -> float:
        """FLOPs one generated token costs at context length ``ctx``
        (attention + projections + LM head) — the honest replacement
        for ``2 * params``."""
        return (self.linear_flops_per_token +
                self.clamp_span(ctx + 1) * self.attn_flops_per_token_kv +
                self.lm_head_flops_per_row)

    def decode_step_bytes(self, batch: int, ctx: float) -> float:
        """HBM bytes one decode step of ``batch`` sequences at context
        ``ctx`` must stream (weights once + per-sequence KV window +
        state + activations)."""
        c = self.wave_cost(batch, batch * self.clamp_span(ctx + 1), batch)
        return c.total_bytes

    def mfu(self, flops: float, device_seconds: float) -> float:
        if device_seconds <= 0:
            return 0.0
        return flops / (device_seconds * self.peak_flops)

    def mbu(self, total_bytes: float, device_seconds: float) -> float:
        if device_seconds <= 0:
            return 0.0
        return total_bytes / (device_seconds * self.peak_hbm)

    # ------------------------------------------------------------------
    @classmethod
    def from_model(cls, model: Any, config: Any,
                   mesh=None) -> "CostModel":
        """Build from a constructed model + engine config (called once
        in models/loader.get_model). Never raises — an arch this
        arithmetic cannot price returns None from the caller's
        perspective via the exception guard there."""
        arch = model.cfg
        page_size = config.cache_config.block_size
        kv_row = 0.0
        try:
            shards = int(getattr(arch, "tpla_shards", 1) or 1)
            kv_row = (model.kv_cache_page_bytes(page_size) / page_size
                      * max(shards, 1))
        except Exception:  # noqa: BLE001 - families without paged KV
            kv_row = 0.0
        n_chips = 1
        device_kind = ""
        try:
            if mesh is not None:
                devices = list(mesh.devices.flat)
                n_chips = len(devices)
                device_kind = getattr(devices[0], "device_kind",
                                      devices[0].platform)
        except Exception:  # noqa: BLE001 - defensive
            pass
        # Hybrid SSM stacks: the model knows its layer kinds.
        attn_layers = None
        if getattr(arch, "stateful", False):
            a = getattr(model, "_attn_layers", None)
            if a is not None:
                attn_layers = len(a)
        return cls.from_arch(arch, kv_row_bytes=kv_row, num_chips=n_chips,
                             device_kind=device_kind,
                             attn_layers=attn_layers)

    @classmethod
    def from_arch(cls, arch: Any, *, kv_row_bytes: float,
                  num_chips: int = 1, device_kind: str = "",
                  attn_layers: Optional[int] = None) -> "CostModel":
        g = lambda k, d=None: getattr(arch, k, d)  # noqa: E731
        H = int(g("hidden_size"))
        L = int(g("num_layers"))
        I = int(g("intermediate_size"))  # noqa: E741
        V = int(g("vocab_size"))
        hd = int(g("head_dim") or H // int(g("num_q_heads", 1)))
        NQ = int(g("num_q_heads", 1))
        NKV = int(g("num_kv_heads", NQ))
        import jax.numpy as jnp
        dtype_bytes = jnp.dtype(g("dtype", jnp.float32)).itemsize
        quant = g("quantization")
        w_bytes = 1 if quant in ("int8", "w8a8", "fp8") else dtype_bytes
        mla = bool(g("mla", False))
        stateful = bool(g("stateful", False))
        gated = bool(g("mlp_gated", True))
        mlp_mults = 3 if gated else 2

        # Layer-kind split: attention layers vs SSM layers; MoE layers
        # vs dense-MLP layers.
        if stateful:
            n_attn = (attn_layers if attn_layers is not None
                      else (0 if kv_row_bytes == 0 else L))
        else:
            n_attn = L
        n_ssm = L - n_attn if stateful else 0
        E = int(g("num_experts", 0) or 0)
        topk = int(g("num_experts_per_tok", 0) or 0)
        # Layers carrying a dense FFN vs routed MoE FFN. Pure-SSM stacks
        # carry no FFN at all (the mamba block is mixer-only); hybrid
        # stacks without MoE keep the FFN on their attention layers.
        moe_layers = 0
        if E:
            dense_head = max(int(g("first_k_dense_replace", 0) or 0),
                             int(g("dense_prefix", 0) or 0))
            dense_mlp_layers = min(dense_head, L)
            moe_layers = L - dense_mlp_layers
        elif stateful:
            dense_mlp_layers = n_attn
        else:
            dense_mlp_layers = L
        Im = int(g("moe_intermediate_size") or I)
        shared_I = int(g("shared_expert_intermediate_size", 0) or 0)

        # -- per-token projection + MLP FLOPs (2 flops per mult-add) ---
        if mla:
            Lkv = int(g("kv_lora_rank"))
            dr = int(g("qk_rope_head_dim"))
            dn = int(g("qk_nope_head_dim"))
            dv = int(g("v_head_dim"))
            qlr = g("q_lora_rank")
            q_proj = ((2 * H * qlr + 2 * qlr * NQ * (dn + dr))
                      if qlr else 2 * H * NQ * (dn + dr))
            attn_proj = (q_proj + 2 * H * (Lkv + dr)  # KV down-proj
                         + 2 * NQ * dn * Lkv          # absorbed q·W_UK
                         + 2 * NQ * Lkv * dv          # out·W_UV
                         + 2 * NQ * dv * H)           # o proj
            # Exact TPLA attention: per-rank latent slices are disjoint
            # and the score psum is counted ONCE; the replicated rope
            # score is useful work once (the TP-1 extra copies are
            # layout overhead, excluded from useful FLOPs).
            attn_pair = 2 * NQ * (Lkv + dr) + 2 * NQ * Lkv
        else:
            Dq = NQ * hd
            Dkv = NKV * hd
            attn_proj = 2 * H * (Dq + 2 * Dkv) + 2 * Dq * H
            attn_pair = 4 * NQ * hd  # QK^T + PV per q head
        mlp_dense = mlp_mults * 2 * H * I
        mlp_moe = 0.0
        if E:
            mlp_moe = (topk * mlp_mults * 2 * H * Im  # routed experts
                       + 2 * H * E)                   # router
            if shared_I:
                mlp_moe += mlp_mults * 2 * H * shared_I + 2 * H
        ssm_per_layer = 0.0
        state_bytes = 0.0
        if stateful:
            Di = int(g("d_inner", 0) or g("intermediate_size"))
            N = int(g("ssm_state_size", 16) or 16)
            K = int(g("conv_kernel", 4) or 4)
            R = int(g("dt_rank", max(H // 16, 1)) or 1)
            ssm_per_layer = (2 * H * 2 * Di        # in_proj (x, gate)
                             + 2 * Di * K          # depthwise conv
                             + 2 * Di * (R + 2 * N)  # x_proj
                             + 2 * R * Di          # dt_proj
                             + 6 * Di * N          # selective scan
                             + 2 * Di * H)         # out_proj
            # fp32 recurrence state (conv tail + ssm state) per token.
            state_bytes = n_ssm * (Di * N + Di * (K - 1)) * 4.0

        linear = (n_attn * attn_proj + n_ssm * ssm_per_layer +
                  dense_mlp_layers * mlp_dense + moe_layers * mlp_moe)

        # -- dense weight bytes streamed once per pass ------------------
        if mla:
            qlr = g("q_lora_rank")
            attn_w = ((H * qlr + qlr * NQ * (dn + dr)) if qlr
                      else H * NQ * (dn + dr))
            attn_w += H * (Lkv + dr) + NQ * dn * Lkv + NQ * Lkv * dv
            attn_w += NQ * dv * H
        else:
            attn_w = H * (NQ * hd + 2 * NKV * hd) + NQ * hd * H
        dense_w = n_attn * attn_w * w_bytes
        dense_w += dense_mlp_layers * mlp_mults * H * I * w_bytes
        if E and shared_I:
            dense_w += moe_layers * (mlp_mults * H * shared_I + H * E
                                     ) * w_bytes
        elif E:
            dense_w += moe_layers * H * E * w_bytes  # router table
        if stateful:
            Di = int(g("d_inner", 0) or g("intermediate_size"))
            N = int(g("ssm_state_size", 16) or 16)
            K = int(g("conv_kernel", 4) or 4)
            R = int(g("dt_rank", max(H // 16, 1)) or 1)
            dense_w += n_ssm * (H * 2 * Di + Di * K +
                                Di * (R + 2 * N) + R * Di +
                                Di * N + Di * H) * w_bytes
        dense_w += 2 * L * H * dtype_bytes  # per-layer norms
        dense_w += V * H * w_bytes          # LM head (read per pass)
        expert_bytes = mlp_mults * H * Im * w_bytes if E else 0.0

        window = None
        wp = g("window_pattern")
        if wp and all(wp) and len(set(wp)) == 1:
            window = int(wp[0])
        elif not wp and g("sliding_window"):
            window = int(g("sliding_window"))

        peak_f = peak_flops_per_chip(device_kind)
        peak_b = peak_hbm_per_chip(device_kind)
        return cls(
            linear_flops_per_token=float(linear),
            attn_flops_per_token_kv=float(n_attn * attn_pair),
            lm_head_flops_per_row=float(2 * H * V),
            dense_weight_bytes=float(dense_w),
            moe_layers=moe_layers,
            num_experts=E,
            experts_per_token=topk,
            expert_bytes=float(expert_bytes),
            kv_row_read_bytes=float(kv_row_bytes),
            kv_row_write_bytes=float(kv_row_bytes),
            state_read_bytes_per_token=float(state_bytes),
            state_write_bytes_per_token=float(state_bytes),
            # Residual stream: 2 reads + 2 writes per layer, plus the
            # embedding row gather feeding layer 0.
            act_bytes_per_token=float(4 * L * H * dtype_bytes +
                                      H * dtype_bytes),
            logits_bytes_per_row=float(V * 4),  # fp32 logits
            attn_window=window,
            num_chips=max(num_chips, 1),
            peak_flops=peak_f * max(num_chips, 1),
            peak_hbm=peak_b * max(num_chips, 1),
            meta={
                "device_kind": device_kind or "host",
                "num_chips": max(num_chips, 1),
                "peak_flops_per_chip": peak_f,
                "peak_hbm_per_chip": peak_b,
                "mla": mla,
                "stateful": stateful,
                "moe_layers": moe_layers,
                "attn_window": window,
                "weight_dtype_bytes": w_bytes,
                "kv_row_bytes": float(kv_row_bytes),
            },
        )


def resolve_cost_model(model: Any, config: Any, mesh=None
                       ) -> Optional[CostModel]:
    """Loader hook: build the model's cost model once, honoring the
    VDT_PERF_ATTRIB kill switch. Returns None (plane fully off, zero
    per-step work) when disabled or the arch cannot be priced."""
    from vllm_distributed_tpu import envs
    if not envs.VDT_PERF_ATTRIB:
        return None
    try:
        cm = CostModel.from_model(model, config, mesh=mesh)
    except Exception as e:  # noqa: BLE001 - observability must not
        # take serving down; an unpriceable arch just goes unmetered.
        logger.warning("perf-attribution cost model unavailable for "
                       "this arch (%s); MFU/MBU unmetered", e)
        return None
    if not math.isfinite(cm.linear_flops_per_token):
        return None
    logger.info(
        "perf attribution: %.3f GFLOP/token linear, %.1f kFLOP/tok/kv "
        "attention, %.1f MB weight stream, %.1f B/pos KV row, peak "
        "%.1f TFLOP/s x %d chip(s)",
        cm.linear_flops_per_token / 1e9,
        cm.attn_flops_per_token_kv / 1e3,
        cm.dense_weight_bytes / 1e6, cm.kv_row_read_bytes,
        cm.peak_flops / 1e12 / cm.num_chips, cm.num_chips)
    return cm
