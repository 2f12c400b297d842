"""The ranking server's wire protocol, client side (numpy only).

A copy of the framing documented at the top of ``serving/rpc.py``, so
the load generator speaks it without importing the program (whose
package imports JAX):

    frame   = u32 length | payload
    OP_RANK = u8 0x01 | u32 request_id | u8 tenant_len | tenant
              | u16 k | f64 deadline_rel | u16 n_ctx | n_ctx x i32 | u8 0
    OP_REPLY= u8 0x81 | u32 request_id | u8 status
              ok:    u16 served_k | u8 degraded | k x f32 | k x i32
              error: u8 tenant_len | tenant | u16 msg_len | message
"""
from __future__ import annotations

import struct

import numpy as np

OP_RANK = 0x01
OP_REPLY = 0x81


def rank_frame(request_id: int, ctx: np.ndarray, k: int,
               tenant: str = "") -> bytes:
    """One length-prefixed OP_RANK frame (no deadline, unit weights)."""
    tb = tenant.encode()
    ctx = np.ascontiguousarray(ctx, np.int32)
    payload = b"".join((
        struct.pack("<BIB", OP_RANK, request_id, len(tb)), tb,
        struct.pack("<HdH", k, 0.0, ctx.shape[0]), ctx.tobytes(),
        b"\x00"))
    return struct.pack("<I", len(payload)) + payload


def parse_replies(buf: bytearray):
    """Consume every whole frame at the head of ``buf``; returns a list of
    ``(request_id, status, scores | None, slots | None)``."""
    out = []
    off = 0
    while len(buf) - off >= 4:
        (n,) = struct.unpack_from("<I", buf, off)
        if len(buf) - off - 4 < n:
            break
        p = off + 4
        op, rid, status = struct.unpack_from("<BIB", buf, p)
        if op != OP_REPLY:
            raise ValueError(f"opcode {op:#x} is not OP_REPLY")
        if status == 0:
            (k,) = struct.unpack_from("<H", buf, p + 6)
            scores = np.frombuffer(buf, np.float32, k, p + 9).copy()
            slots = np.frombuffer(buf, np.int32, k, p + 9 + 4 * k).copy()
            out.append((rid, 0, scores, slots))
        else:
            out.append((rid, status, None, None))
        off = p + n
    del buf[:off]
    return out
