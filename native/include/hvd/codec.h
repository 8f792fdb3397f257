// On-the-wire gradient compression for the TCP data plane.
//
// EQuARX (arXiv:2506.17615) shows quantized allreduce roughly doubles
// effective interconnect bandwidth at negligible accuracy cost; this is
// the host-plane rebuild of that idea for the ring/doubling exchanges
// in ops.cc. Three codecs over FLOAT32 payloads:
//
//  * BF16 — truncate-with-round to bfloat16 (same exponent range as
//    f32; the TPU-native wire format). 2x smaller.
//  * FP16 — IEEE half with round-to-nearest-even. 2x smaller, more
//    mantissa but less range than bf16.
//  * INT8 — blockwise-scaled int8: each 256-element block carries a
//    float absmax/127 scale followed by the quantized bytes (~3.9x
//    smaller). Optionally error-feedback compensated: the caller keeps
//    a rank-local residual that is added before quantization and
//    updated with the new rounding error, so quantization error is
//    carried into the next step instead of being dropped (EF-SGD).
//
// Determinism contract (same as HostAccumulate): encode/decode chunk
// the work over the WorkerPool at element/block granularity with a
// pure per-range split, so the produced bytes are bitwise identical at
// any thread count.
#pragma once

#include <cstdint>

namespace hvd {

// Wire-stable codec ids (ride Request/Response and the tuned-params
// broadcast; also the HOROVOD_WIRE_COMPRESSION choice indices).
enum class WireCodec : uint8_t {
  NONE = 0,
  BF16 = 1,
  FP16 = 2,
  INT8 = 3,
};

// Canonical codec names, indexed by WireCodec value — the single
// source for both WireCodecName and the HOROVOD_WIRE_COMPRESSION
// choice parse, so the env indices can never skew from the enum.
constexpr const char* kWireCodecNames[] = {"none", "bf16", "fp16", "int8"};
constexpr int kNumWireCodecs = 4;

const char* WireCodecName(WireCodec c);

// Elements per int8 quantization block (one float scale per block).
constexpr int64_t kInt8BlockElems = 256;

inline int64_t Int8Blocks(int64_t elems) {
  return (elems + kInt8BlockElems - 1) / kInt8BlockElems;
}

// Encoded byte count for `elems` float32 elements. NONE reports the
// raw size (callers never ship NONE through the codec, but the ratio
// math in tests reads this).
int64_t WireEncodedBytes(WireCodec codec, int64_t elems);

// Encode `elems` floats from src into dst (WireEncodedBytes bytes).
// `residual` (nullable; INT8 only) is the rank-local error-feedback
// buffer of `elems` floats: the value quantized is src[i]+residual[i]
// and residual[i] is updated to the new rounding error.
void WireEncode(WireCodec codec, const float* src, int64_t elems,
                uint8_t* dst, float* residual);

// Decode `elems` floats from src into dst. dst := decoded.
void WireDecode(WireCodec codec, const uint8_t* src, int64_t elems,
                float* dst);

// Fused decode-accumulate: dst[i] += decoded[i] (the ring's
// reduce-scatter hot path — one pass instead of decode + add).
void WireDecodeAdd(WireCodec codec, const uint8_t* src, int64_t elems,
                   float* dst);

// Fully-fused ring relay step: enc_out := Encode(Decode(enc_in) + add)
// without materializing the fp32 sum. The ring reduce-scatter forwards
// most chunks immediately after accumulating them — the fp32 form is
// dead the moment the encoded bytes leave, so skipping its store/load
// halves the compressed hot loop's memory traffic (what makes wire
// compression win even on CPU-bound loopback). `residual` as in
// WireEncode (INT8 error feedback over the summed value).
void WireDecodeAddEncode(WireCodec codec, const uint8_t* enc_in,
                         const float* add, int64_t elems, uint8_t* enc_out,
                         float* residual);

}  // namespace hvd
