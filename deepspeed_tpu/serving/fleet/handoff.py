"""KV handoff — serialized prefill state crossing replica boundaries.

Disaggregated serving splits the two phases of a request across replica
roles: *prefill* replicas run the compute-bound prompt pass, *decode*
replicas run the bandwidth-bound token loop. The boundary object is
``KVHandoff``: one slot lane (the prompt's K/V), the sampled first
token, and enough request metadata for the decode side to continue
byte-for-byte where prefill stopped.

Transport is pluggable. In-process fleets (``ds_tpu_serve --fleet``)
pass the lane as host numpy arrays — ``slot_extract_lane`` on the
prefill pool, ``slot_insert_lane`` into the decode pool. The
``to_bytes``/``from_bytes`` codec frames the same payload for a real
interconnect later (ICI/RDMA or TCP between hosts): a JSON header (shapes,
dtypes, metadata) plus raw little-endian buffers in header order, so a
receiver can post fixed-size receives without parsing numpy containers.
Quantized pools hand off their int8 q + f32 scale slices directly — the
wire cost of a disaggregated transfer is the *quantized* lane, ~4x
smaller, with zero extra quantization error (the decode pool inserts the
slices verbatim).
"""

import dataclasses
import json
import struct
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["KVHandoff", "InProcessTransport"]

_MAGIC = b"DSKV1\n"


def _flatten_lane(lane) -> Tuple[List[Tuple[str, np.ndarray]], bool]:
    """(ordered (path, array) pairs, quantized?) for any lane flavor."""
    from ...inference.kv_quant import QuantizedSlotPool
    if isinstance(lane, QuantizedSlotPool):
        pairs = [(f"q/{k}", np.asarray(v))
                 for k, v in sorted(lane.q.items())]
        pairs += [(f"scales/{k}", np.asarray(v))
                  for k, v in sorted(lane.scales.items())]
        return pairs, True
    return [(k, np.asarray(v)) for k, v in sorted(lane.items())], False


def _unflatten_lane(pairs: Dict[str, np.ndarray], quantized: bool):
    if not quantized:
        return dict(pairs)
    from ...inference.kv_quant import QuantizedSlotPool
    q = {k[len("q/"):]: v for k, v in pairs.items() if k.startswith("q/")}
    s = {k[len("scales/"):]: v for k, v in pairs.items()
         if k.startswith("scales/")}
    return QuantizedSlotPool(q=q, scales=s)


@dataclasses.dataclass
class KVHandoff:
    """One completed prefill, ready for a decode pool.

    ``lane`` is a host pytree shaped like one pool slot (``[L, 1,
    max_len, H, hd]`` leaves as ``init_kv_cache`` shapes them, or the
    q/scales pair for quantized pools; a frame carries each leaf's shape);
    ``kv_len`` says how many columns are valid — the insert copies the
    whole lane and the decode mask never reads past ``kv_len`` until the
    columns are rewritten. ``first_token`` was already sampled (and
    delivered — TTFT happens on the prefill side); decode feeds it at
    column ``kv_len``."""
    prompt: np.ndarray              # int32 [T] — the prefilled tokens
    first_token: int
    kv_len: int                     # valid cache columns (== len(prompt))
    lane: Any                       # host lane pytree (fp or quantized)
    temperature: float = 0.0
    #: sampling law (with temperature + seed): the decode side must
    #: reproduce the prefill side's stream bit-for-bit, so the full
    #: replay law crosses the wire in the frame header
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    max_new_tokens: int = 64
    eos_token_id: Optional[int] = None
    request_id: Optional[int] = None
    source: Optional[str] = None    # producing replica name
    #: the tenant this request bills to — survives disaggregation so the
    #: decode side's per-tenant SLO windows and DRR admission see the
    #: same tenant the prefill side admitted under
    tenant: Optional[str] = None
    #: distributed trace context header (TraceContext.to_header()) — the
    #: request's fleet-wide identity rides the frame so the decode side
    #: continues the SAME trace, not a fresh one
    trace: Optional[Dict[str, Any]] = None
    #: the producing replica's weights_version: a decode replica whose
    #: own version differs REFUSES the lane (re-prefills locally) — KV
    #: from one model fed through another is silent corruption, and a
    #: mid-rollout fleet is exactly when versions differ. ``None`` means
    #: a pre-rollout producer (accepted for compatibility).
    weights_version: Optional[int] = None

    # ------------------------------------------------------------- framing
    def to_bytes(self) -> bytes:
        """RDMA-shaped framing: magic, u32 header length, JSON header,
        then raw buffers in header order."""
        pairs, quantized = _flatten_lane(self.lane)
        header = {
            "prompt": [int(t) for t in np.asarray(self.prompt).reshape(-1)],
            "first_token": int(self.first_token),
            "kv_len": int(self.kv_len),
            "temperature": float(self.temperature),
            "top_k": int(self.top_k),
            "top_p": float(self.top_p),
            "seed": int(self.seed),
            "max_new_tokens": int(self.max_new_tokens),
            "eos_token_id": self.eos_token_id,
            "request_id": self.request_id,
            "source": self.source,
            "tenant": self.tenant,
            "trace": self.trace,
            "weights_version": self.weights_version,
            "quantized": quantized,
            "buffers": [{"path": p, "dtype": a.dtype.str,
                         "shape": list(a.shape)} for p, a in pairs],
        }
        hdr = json.dumps(header).encode("utf-8")
        out = [_MAGIC, struct.pack("<I", len(hdr)), hdr]
        out += [np.ascontiguousarray(a).tobytes() for _p, a in pairs]
        return b"".join(out)

    @classmethod
    def from_bytes(cls, blob: bytes) -> "KVHandoff":
        if blob[:len(_MAGIC)] != _MAGIC:
            raise ValueError("not a KVHandoff frame (bad magic)")
        off = len(_MAGIC)
        (hlen,) = struct.unpack_from("<I", blob, off)
        off += 4
        header = json.loads(blob[off:off + hlen].decode("utf-8"))
        off += hlen
        pairs = {}
        for buf in header["buffers"]:
            dt = np.dtype(buf["dtype"])
            n = int(np.prod(buf["shape"])) if buf["shape"] else 1
            arr = np.frombuffer(blob, dtype=dt, count=n, offset=off)
            pairs[buf["path"]] = arr.reshape(buf["shape"])
            off += n * dt.itemsize
        return cls(
            prompt=np.asarray(header["prompt"], np.int32),
            first_token=header["first_token"],
            kv_len=header["kv_len"],
            lane=_unflatten_lane(pairs, header["quantized"]),
            temperature=header["temperature"],
            top_k=header.get("top_k", 0),
            top_p=header.get("top_p", 1.0),
            seed=header.get("seed", 0),
            max_new_tokens=header["max_new_tokens"],
            eos_token_id=header["eos_token_id"],
            request_id=header["request_id"],
            source=header["source"],
            tenant=header.get("tenant"),
            trace=header.get("trace"),
            weights_version=header.get("weights_version"))

    def nbytes(self) -> int:
        """Payload bytes a transport would move (lane buffers only)."""
        pairs, _q = _flatten_lane(self.lane)
        return sum(a.nbytes for _p, a in pairs)


class InProcessTransport:
    """The trivial transport: deliver the handoff object to a sink
    callable in the same process. Exists so the router is written against
    ``transport.send(handoff, request)`` — an RDMA/TCP transport swaps in
    behind the same call, shipping ``handoff.to_bytes()``."""

    def __init__(self, sink: Callable[[KVHandoff, Any], None]):
        self._sink = sink
        self.sent = 0
        self.bytes_moved = 0

    def send(self, handoff: KVHandoff, request: Any = None):
        self.sent += 1
        self.bytes_moved += handoff.nbytes()
        self._sink(handoff, request)
