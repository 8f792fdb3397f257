#include "hvd/codec.h"

#include <algorithm>
#include <cmath>
#include <cstring>

#include "hvd/half.h"
#include "hvd/metrics.h"
#include "hvd/thread_pool.h"

#if defined(__x86_64__) && defined(__GNUC__)
#include <cpuid.h>
#include <immintrin.h>
#define HVD_F16C_DISPATCH 1
#endif

namespace hvd {

namespace {

// ---- serial kernels (pure per element/block range, so the threaded
// fronts below are bitwise invariant to the thread count) -------------
//
// The bf16 bodies are branch-free shift/add bit math, so the compiler
// auto-vectorizes them; HVD_CLONES lets it emit an AVX2 clone behind a
// runtime dispatch while the default build stays baseline-x86-64 (the
// .so must run on any host of a heterogeneous fleet — same policy as
// the Makefile's opt-in MARCH).
#if defined(__x86_64__) && defined(__GNUC__) && !defined(__clang__)
#define HVD_CLONES __attribute__((target_clones("avx2", "default")))
#else
#define HVD_CLONES
#endif

template <uint16_t (*FromF)(float)>
void Encode16Serial(const float* src, uint16_t* dst, int64_t n) {
  for (int64_t i = 0; i < n; ++i) dst[i] = FromF(src[i]);
}

template <float (*ToF)(uint16_t)>
void Decode16Serial(const uint16_t* src, float* dst, int64_t n) {
  for (int64_t i = 0; i < n; ++i) dst[i] = ToF(src[i]);
}

template <float (*ToF)(uint16_t)>
void Decode16AddSerial(const uint16_t* src, float* dst, int64_t n) {
  for (int64_t i = 0; i < n; ++i) dst[i] += ToF(src[i]);
}

// Concrete bf16 fronts for the clone attribute (templates can't carry
// target_clones).
HVD_CLONES void Bf16Encode(const float* src, uint16_t* dst, int64_t n) {
  Encode16Serial<Float2BFloat>(src, dst, n);
}
HVD_CLONES void Bf16Decode(const uint16_t* src, float* dst, int64_t n) {
  Decode16Serial<BFloat2Float>(src, dst, n);
}
HVD_CLONES void Bf16DecodeAdd(const uint16_t* src, float* dst, int64_t n) {
  Decode16AddSerial<BFloat2Float>(src, dst, n);
}
HVD_CLONES void Bf16Relay(const uint16_t* in, const float* add,
                          uint16_t* out, int64_t n) {
  for (int64_t i = 0; i < n; ++i)
    out[i] = Float2BFloat(BFloat2Float(in[i]) + add[i]);
}

#ifdef HVD_F16C_DISPATCH
// Hardware fp16 converters (runtime-dispatched: the default build must
// run on any x86-64 host, but the scalar Float2HalfBits is too branchy
// to vectorize — 0.5 GB/s, slower than the loopback socket it is
// meant to relieve). vcvtps2ph/vcvtph2ps implement the same IEEE
// round-to-nearest-even as the scalar path, and the tails use the
// hardware SCALAR ops so the produced bytes never depend on where a
// thread split lands.
__attribute__((target("f16c"))) void F16CEncode(const float* src,
                                                uint16_t* dst, int64_t n) {
  int64_t i = 0;
  for (; i + 8 <= n; i += 8) {
    __m128i h = _mm256_cvtps_ph(_mm256_loadu_ps(src + i),
                                _MM_FROUND_TO_NEAREST_INT);
    _mm_storeu_si128(reinterpret_cast<__m128i*>(dst + i), h);
  }
  for (; i < n; ++i) dst[i] = _cvtss_sh(src[i], _MM_FROUND_TO_NEAREST_INT);
}

__attribute__((target("f16c"))) void F16CDecode(const uint16_t* src,
                                                float* dst, int64_t n,
                                                bool add) {
  int64_t i = 0;
  if (add) {
    for (; i + 8 <= n; i += 8) {
      __m256 v = _mm256_cvtph_ps(
          _mm_loadu_si128(reinterpret_cast<const __m128i*>(src + i)));
      _mm256_storeu_ps(dst + i, _mm256_add_ps(_mm256_loadu_ps(dst + i), v));
    }
    for (; i < n; ++i) dst[i] += _cvtsh_ss(src[i]);
  } else {
    for (; i + 8 <= n; i += 8) {
      __m256 v = _mm256_cvtph_ps(
          _mm_loadu_si128(reinterpret_cast<const __m128i*>(src + i)));
      _mm256_storeu_ps(dst + i, v);
    }
    for (; i < n; ++i) dst[i] = _cvtsh_ss(src[i]);
  }
}

__attribute__((target("f16c"))) void F16CRelay(const uint16_t* in,
                                               const float* add,
                                               uint16_t* out, int64_t n) {
  int64_t i = 0;
  for (; i + 8 <= n; i += 8) {
    __m256 v = _mm256_cvtph_ps(
        _mm_loadu_si128(reinterpret_cast<const __m128i*>(in + i)));
    __m128i h = _mm256_cvtps_ph(_mm256_add_ps(v, _mm256_loadu_ps(add + i)),
                                _MM_FROUND_TO_NEAREST_INT);
    _mm_storeu_si128(reinterpret_cast<__m128i*>(out + i), h);
  }
  for (; i < n; ++i)
    out[i] = _cvtss_sh(_cvtsh_ss(in[i]) + add[i], _MM_FROUND_TO_NEAREST_INT);
}

bool HasF16C() {
  // CPUID.1:ECX bit 29 ("f16c" is missing from this toolchain's
  // __builtin_cpu_supports feature list, so read the bit directly).
  static const bool has = [] {
    unsigned a, b, c, d;
    return __get_cpuid(1, &a, &b, &c, &d) && (c & (1u << 29));
  }();
  return has;
}
#else
inline bool HasF16C() { return false; }
#endif

// Branchless round-to-nearest-even for |x| <= 2^22: adding 1.5*2^23
// snaps the mantissa to integer granularity under the default rounding
// mode, and the biased bit pattern minus the magic constant IS the
// rounded integer (two's complement covers negatives). Bit-identical
// to lrintf on this range, but a plain fp add the compiler vectorizes
// — lrintf stays scalar and was the int8 encode bottleneck (0.8 GB/s
// vs the 1.2 GB/s loopback socket it was supposed to relieve).
inline int32_t RoundNearestSmall(float x) {
  float f = x + 12582912.0f;
  int32_t i;
  std::memcpy(&i, &f, 4);
  return i - 0x4B400000;
}

// Int8 wire layout for `elems` values: [float scales[Int8Blocks]]
// [int8 q[elems]]. Block b covers elements [b*256, min(elems, b*256+256)):
// scale = absmax/127 (0 for an all-zero block), q = round(v/scale)
// clamped to [-127, 127]. With error feedback, v = src + residual and
// the new residual is v - q*scale — the exact rounding error, carried
// into the next encode at this site.
HVD_CLONES
void Int8EncodeBlocks(const float* src, int64_t elems, float* scales,
                      int8_t* q, float* residual, int64_t blo, int64_t bhi) {
  for (int64_t b = blo; b < bhi; ++b) {
    const int64_t lo = b * kInt8BlockElems;
    const int64_t hi = std::min(elems, lo + kInt8BlockElems);
    float absmax = 0.0f;
    if (residual) {
      for (int64_t i = lo; i < hi; ++i)
        absmax = std::max(absmax, std::fabs(src[i] + residual[i]));
    } else {
      for (int64_t i = lo; i < hi; ++i)
        absmax = std::max(absmax, std::fabs(src[i]));
    }
    const float scale = absmax > 0.0f ? absmax / 127.0f : 0.0f;
    const float inv = scale > 0.0f ? 1.0f / scale : 0.0f;
    scales[b] = scale;
    // absmax*inv can land a hair above 127 after rounding, so clamp.
    // Residual handling is hoisted out of the loop so both bodies stay
    // branch-free and vectorizable.
    if (residual) {
      for (int64_t i = lo; i < hi; ++i) {
        const float v = src[i] + residual[i];
        int32_t qi = RoundNearestSmall(v * inv);
        qi = std::max(-127, std::min(127, qi));
        q[i] = static_cast<int8_t>(qi);
        residual[i] = v - static_cast<float>(qi) * scale;
      }
    } else {
      for (int64_t i = lo; i < hi; ++i) {
        int32_t qi = RoundNearestSmall(src[i] * inv);
        qi = std::max(-127, std::min(127, qi));
        q[i] = static_cast<int8_t>(qi);
      }
    }
  }
}

HVD_CLONES
void Int8DecodeBlocks(const float* scales, const int8_t* q, int64_t elems,
                      float* dst, int64_t blo, int64_t bhi, bool add) {
  for (int64_t b = blo; b < bhi; ++b) {
    const int64_t lo = b * kInt8BlockElems;
    const int64_t hi = std::min(elems, lo + kInt8BlockElems);
    const float scale = scales[b];
    if (add) {
      for (int64_t i = lo; i < hi; ++i)
        dst[i] += static_cast<float>(q[i]) * scale;
    } else {
      for (int64_t i = lo; i < hi; ++i)
        dst[i] = static_cast<float>(q[i]) * scale;
    }
  }
}

// Run fn over [0, n) units, split across the worker pool when the
// payload (bytes) clears the parallel grain. Int8 passes blocks as the
// unit so every split lands on a block boundary (scales are per block).
template <typename F>
void ParallelUnits(int64_t n, int64_t bytes, F&& fn) {
  const int parts = ParallelParts(bytes);
  if (parts <= 1 || n <= 1) {
    fn(0, n);
    return;
  }
  WorkerPool::Get().ParallelFor(parts, n, fn);
}

}  // namespace

const char* WireCodecName(WireCodec c) {
  const int i = static_cast<int>(c);
  return i >= 0 && i < kNumWireCodecs ? kWireCodecNames[i] : "?";
}

int64_t WireEncodedBytes(WireCodec codec, int64_t elems) {
  switch (codec) {
    case WireCodec::NONE:
      return elems * 4;
    case WireCodec::BF16:
    case WireCodec::FP16:
      return elems * 2;
    case WireCodec::INT8:
      return Int8Blocks(elems) * static_cast<int64_t>(sizeof(float)) + elems;
  }
  return elems * 4;
}

namespace {

// Pre/post wire byte accounting for every encode site (plain and
// relay-fused): a saving derived from these two counters is the bytes
// that actually skipped the wire, not a ratio recomputed from
// assumptions.
inline void RecordEncodeMetrics(WireCodec codec, int64_t elems) {
  if (codec == WireCodec::NONE) return;
  MetricAdd(kCtrWireEncodes);
  MetricAdd(kCtrWirePreBytes, elems * 4);
  MetricAdd(kCtrWirePostBytes, WireEncodedBytes(codec, elems));
}

}  // namespace

void WireEncode(WireCodec codec, const float* src, int64_t elems,
                uint8_t* dst, float* residual) {
  if (elems <= 0) return;
  RecordEncodeMetrics(codec, elems);
  switch (codec) {
    case WireCodec::NONE:
      std::memcpy(dst, src, elems * 4);
      return;
    case WireCodec::BF16:
      ParallelUnits(elems, elems * 4, [&](int64_t lo, int64_t hi) {
        Bf16Encode(src + lo, reinterpret_cast<uint16_t*>(dst) + lo, hi - lo);
      });
      return;
    case WireCodec::FP16:
      ParallelUnits(elems, elems * 4, [&](int64_t lo, int64_t hi) {
        uint16_t* out = reinterpret_cast<uint16_t*>(dst) + lo;
#ifdef HVD_F16C_DISPATCH
        if (HasF16C()) {
          F16CEncode(src + lo, out, hi - lo);
          return;
        }
#endif
        Encode16Serial<Float2HalfBits>(src + lo, out, hi - lo);
      });
      return;
    case WireCodec::INT8: {
      auto* scales = reinterpret_cast<float*>(dst);
      auto* q = reinterpret_cast<int8_t*>(dst + Int8Blocks(elems) *
                                                    sizeof(float));
      ParallelUnits(Int8Blocks(elems), elems * 4,
                    [&](int64_t blo, int64_t bhi) {
                      Int8EncodeBlocks(src, elems, scales, q, residual, blo,
                                       bhi);
                    });
      return;
    }
  }
}

namespace {

void DecodeImpl(WireCodec codec, const uint8_t* src, int64_t elems,
                float* dst, bool add) {
  if (elems <= 0) return;
  switch (codec) {
    case WireCodec::NONE: {
      const float* s = reinterpret_cast<const float*>(src);
      ParallelUnits(elems, elems * 4, [&](int64_t lo, int64_t hi) {
        if (add) {
          for (int64_t i = lo; i < hi; ++i) dst[i] += s[i];
        } else {
          std::memcpy(dst + lo, s + lo, (hi - lo) * 4);
        }
      });
      return;
    }
    case WireCodec::BF16:
      ParallelUnits(elems, elems * 4, [&](int64_t lo, int64_t hi) {
        const uint16_t* s = reinterpret_cast<const uint16_t*>(src) + lo;
        if (add) {
          Bf16DecodeAdd(s, dst + lo, hi - lo);
        } else {
          Bf16Decode(s, dst + lo, hi - lo);
        }
      });
      return;
    case WireCodec::FP16:
      ParallelUnits(elems, elems * 4, [&](int64_t lo, int64_t hi) {
        const uint16_t* s = reinterpret_cast<const uint16_t*>(src) + lo;
#ifdef HVD_F16C_DISPATCH
        if (HasF16C()) {
          F16CDecode(s, dst + lo, hi - lo, add);
          return;
        }
#endif
        if (add) {
          Decode16AddSerial<HalfBits2Float>(s, dst + lo, hi - lo);
        } else {
          Decode16Serial<HalfBits2Float>(s, dst + lo, hi - lo);
        }
      });
      return;
    case WireCodec::INT8: {
      const auto* scales = reinterpret_cast<const float*>(src);
      const auto* q = reinterpret_cast<const int8_t*>(
          src + Int8Blocks(elems) * sizeof(float));
      ParallelUnits(Int8Blocks(elems), elems * 4,
                    [&](int64_t blo, int64_t bhi) {
                      Int8DecodeBlocks(scales, q, elems, dst, blo, bhi, add);
                    });
      return;
    }
  }
}

}  // namespace

void WireDecode(WireCodec codec, const uint8_t* src, int64_t elems,
                float* dst) {
  DecodeImpl(codec, src, elems, dst, /*add=*/false);
}

void WireDecodeAdd(WireCodec codec, const uint8_t* src, int64_t elems,
                   float* dst) {
  DecodeImpl(codec, src, elems, dst, /*add=*/true);
}

namespace {

template <float (*ToF)(uint16_t), uint16_t (*FromF)(float)>
void Relay16Serial(const uint16_t* in, const float* add, uint16_t* out,
                   int64_t lo, int64_t hi) {
  for (int64_t i = lo; i < hi; ++i)
    out[i] = FromF(ToF(in[i]) + add[i]);
}

// Int8 relay: per block, materialize the summed values in a
// block-sized (cache-resident) stack buffer for the absmax pass, then
// quantize out of it — the fp32 chunk never touches main memory.
HVD_CLONES
void Int8RelayBlocks(const float* in_scales, const int8_t* in_q,
                     const float* add, int64_t elems, float* out_scales,
                     int8_t* out_q, float* residual, int64_t blo,
                     int64_t bhi) {
  float v[kInt8BlockElems];
  for (int64_t b = blo; b < bhi; ++b) {
    const int64_t lo = b * kInt8BlockElems;
    const int64_t n = std::min(elems - lo, kInt8BlockElems);
    const float in_scale = in_scales[b];
    float absmax = 0.0f;
    if (residual) {
      for (int64_t j = 0; j < n; ++j) {
        float s = static_cast<float>(in_q[lo + j]) * in_scale + add[lo + j] +
                  residual[lo + j];
        v[j] = s;
        absmax = std::max(absmax, std::fabs(s));
      }
    } else {
      for (int64_t j = 0; j < n; ++j) {
        float s = static_cast<float>(in_q[lo + j]) * in_scale + add[lo + j];
        v[j] = s;
        absmax = std::max(absmax, std::fabs(s));
      }
    }
    const float scale = absmax > 0.0f ? absmax / 127.0f : 0.0f;
    const float inv = scale > 0.0f ? 1.0f / scale : 0.0f;
    out_scales[b] = scale;
    if (residual) {
      for (int64_t j = 0; j < n; ++j) {
        int32_t qi = RoundNearestSmall(v[j] * inv);
        qi = std::max(-127, std::min(127, qi));
        out_q[lo + j] = static_cast<int8_t>(qi);
        residual[lo + j] = v[j] - static_cast<float>(qi) * scale;
      }
    } else {
      for (int64_t j = 0; j < n; ++j) {
        int32_t qi = RoundNearestSmall(v[j] * inv);
        qi = std::max(-127, std::min(127, qi));
        out_q[lo + j] = static_cast<int8_t>(qi);
      }
    }
  }
}

}  // namespace

void WireDecodeAddEncode(WireCodec codec, const uint8_t* enc_in,
                         const float* add, int64_t elems, uint8_t* enc_out,
                         float* residual) {
  if (elems <= 0) return;
  RecordEncodeMetrics(codec, elems);
  switch (codec) {
    case WireCodec::NONE: {
      const float* in = reinterpret_cast<const float*>(enc_in);
      float* out = reinterpret_cast<float*>(enc_out);
      ParallelUnits(elems, elems * 4, [&](int64_t lo, int64_t hi) {
        for (int64_t i = lo; i < hi; ++i) out[i] = in[i] + add[i];
      });
      return;
    }
    case WireCodec::BF16:
      ParallelUnits(elems, elems * 4, [&](int64_t lo, int64_t hi) {
        Bf16Relay(reinterpret_cast<const uint16_t*>(enc_in) + lo, add + lo,
                  reinterpret_cast<uint16_t*>(enc_out) + lo, hi - lo);
      });
      return;
    case WireCodec::FP16:
      ParallelUnits(elems, elems * 4, [&](int64_t lo, int64_t hi) {
#ifdef HVD_F16C_DISPATCH
        if (HasF16C()) {
          F16CRelay(reinterpret_cast<const uint16_t*>(enc_in) + lo,
                    add + lo, reinterpret_cast<uint16_t*>(enc_out) + lo,
                    hi - lo);
          return;
        }
#endif
        Relay16Serial<HalfBits2Float, Float2HalfBits>(
            reinterpret_cast<const uint16_t*>(enc_in), add,
            reinterpret_cast<uint16_t*>(enc_out), lo, hi);
      });
      return;
    case WireCodec::INT8: {
      const int64_t nb = Int8Blocks(elems);
      const auto* in_scales = reinterpret_cast<const float*>(enc_in);
      const auto* in_q =
          reinterpret_cast<const int8_t*>(enc_in + nb * sizeof(float));
      auto* out_scales = reinterpret_cast<float*>(enc_out);
      auto* out_q = reinterpret_cast<int8_t*>(enc_out + nb * sizeof(float));
      ParallelUnits(nb, elems * 4, [&](int64_t blo, int64_t bhi) {
        Int8RelayBlocks(in_scales, in_q, add, elems, out_scales, out_q,
                        residual, blo, bhi);
      });
      return;
    }
  }
}

}  // namespace hvd
