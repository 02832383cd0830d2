// Native data-pipeline kernels: JPEG decode + batched transform.
//
// TPU-native equivalent of the reference's native image path —
// cv::imdecode via jcaffe Mat (caffe-distri/src/main/cpp/jni/JniMat.cpp)
// and caffe::DataTransformer via FloatDataTransformer
// (jni/JniFloatDataTransformer.cpp) — feeding preallocated NCHW float
// buffers.  Exposed as a plain C ABI for ctypes (no pybind11 in this
// image).  Threading: every call spreads the batch's images over
// `num_threads` threads, the caller's own among them (0 = one per
// hardware thread; a transformer pool hands each of its workers a share
// of the cores, data/queue_runner.py:tune_decode_threads).
//
// Layout notes: decode emits BGR channel order (OpenCV convention, which
// Caffe models expect) as planar CHW, uint8 or float32.  An image already
// at the requested size is only deinterleaved; any other is resized
// bilinearly.

#include <cstddef>
#include <cstdio>

#include <jpeglib.h>

#include <algorithm>
#include <atomic>
#include <cmath>
#include <csetjmp>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

namespace {

// `worker` drains a shared counter of n items: run it on num_threads
// threads (0 = one per hardware thread, never more than n).  The
// calling thread is one of them and the other num_threads - 1 are
// spawned: one thread spawns nothing, and the caller's own CPU clock
// (a pool worker's `pack_cpu`) sees its share of the work.
template <typename F>
void run_workers(int num_threads, int n, F worker) {
  int nthreads = num_threads > 0
                     ? num_threads
                     : static_cast<int>(std::thread::hardware_concurrency());
  nthreads = std::max(1, std::min(nthreads, n));
  std::vector<std::thread> helpers;
  for (int t = 1; t < nthreads; ++t) helpers.emplace_back(worker);
  worker();
  for (auto& t : helpers) t.join();
}

struct JpegErr {
  jpeg_error_mgr pub;
  jmp_buf jmp;
};

void jpeg_err_exit(j_common_ptr cinfo) {
  JpegErr* err = reinterpret_cast<JpegErr*>(cinfo->err);
  longjmp(err->jmp, 1);
}

// decode JPEG bytes to interleaved rows; returns false on corrupt input
bool decode_jpeg_raw(const unsigned char* data, long size, int channels,
                     std::vector<unsigned char>* pixels, int* h, int* w) {
  jpeg_decompress_struct cinfo;
  JpegErr jerr;
  cinfo.err = jpeg_std_error(&jerr.pub);
  jerr.pub.error_exit = jpeg_err_exit;
  if (setjmp(jerr.jmp)) {
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  jpeg_create_decompress(&cinfo);
  jpeg_mem_src(&cinfo, const_cast<unsigned char*>(data),
               static_cast<unsigned long>(size));
  if (jpeg_read_header(&cinfo, TRUE) != JPEG_HEADER_OK) {
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  cinfo.out_color_space = channels == 1 ? JCS_GRAYSCALE : JCS_RGB;
  jpeg_start_decompress(&cinfo);
  *h = cinfo.output_height;
  *w = cinfo.output_width;
  int comps = cinfo.output_components;
  pixels->resize(static_cast<size_t>(*h) * *w * comps);
  while (cinfo.output_scanline < cinfo.output_height) {
    unsigned char* row =
        pixels->data() + static_cast<size_t>(cinfo.output_scanline) * *w * comps;
    jpeg_read_scanlines(&cinfo, &row, 1);
  }
  jpeg_finish_decompress(&cinfo);
  jpeg_destroy_decompress(&cinfo);
  return true;
}

// HWC(RGB) → CHW(BGR), resized bilinearly when the sizes differ.  Dst
// is float or uint8; the uint8 store TRUNCATES (matches numpy
// astype(uint8) on the float output, so the uint8-infeed path equals
// cast(float path) exactly).  At equal sizes every bilinear weight is 1
// or 0 and the resampling is the identity: that branch only
// deinterleaves, to the same values bit for bit.
template <typename T>
void resize_to_chw(const unsigned char* src, int sh, int sw, int channels,
                   int dh, int dw, T* dst) {
  if (sh == dh && sw == dw) {
    const size_t px = static_cast<size_t>(dh) * dw;
    if (channels == 3) {
      T* b = dst;
      T* g = dst + px;
      T* r = dst + 2 * px;
      for (size_t i = 0; i < px; ++i) {
        r[i] = static_cast<T>(src[3 * i]);
        g[i] = static_cast<T>(src[3 * i + 1]);
        b[i] = static_cast<T>(src[3 * i + 2]);
      }
    } else {  // channel order kept (one channel in practice)
      for (int c = 0; c < channels; ++c)
        for (size_t i = 0; i < px; ++i)
          dst[c * px + i] = static_cast<T>(src[i * channels + c]);
    }
    return;
  }
  const float ys = dh > 1 ? static_cast<float>(sh - 1) / (dh - 1) : 0.0f;
  const float xs = dw > 1 ? static_cast<float>(sw - 1) / (dw - 1) : 0.0f;
  for (int y = 0; y < dh; ++y) {
    float fy = y * ys;
    int y0 = static_cast<int>(fy);
    int y1 = std::min(y0 + 1, sh - 1);
    float wy = fy - y0;
    for (int x = 0; x < dw; ++x) {
      float fx = x * xs;
      int x0 = static_cast<int>(fx);
      int x1 = std::min(x0 + 1, sw - 1);
      float wx = fx - x0;
      for (int c = 0; c < channels; ++c) {
        const float p00 = src[(y0 * sw + x0) * channels + c];
        const float p01 = src[(y0 * sw + x1) * channels + c];
        const float p10 = src[(y1 * sw + x0) * channels + c];
        const float p11 = src[(y1 * sw + x1) * channels + c];
        float v = p00 * (1 - wy) * (1 - wx) + p01 * (1 - wy) * wx +
                  p10 * wy * (1 - wx) + p11 * wy * wx;
        // BGR plane order: plane (channels-1-c) receives RGB channel c
        int plane = channels == 3 ? 2 - c : c;
        dst[(static_cast<size_t>(plane) * dh + y) * dw + x] =
            static_cast<T>(v);
      }
    }
  }
}

// `exact`: the caller wants the decoded pixels themselves (a uint8
// store of a resampled image would truncate).  The first image of
// another size then ends the call with -1 and the caller decodes to
// float32 instead.
template <typename T>
int decode_batch_impl(const unsigned char* blob, const long* offsets,
                      const long* sizes, int n, int channels, int out_h,
                      int out_w, T* out, int num_threads,
                      bool exact = false) {
  std::atomic<int> ok(0);
  std::atomic<int> next(0);
  std::atomic<bool> resample(false);
  run_workers(num_threads, n, [&]() {
    std::vector<unsigned char> pixels;
    for (int i = next.fetch_add(1); i < n && !resample.load();
         i = next.fetch_add(1)) {
      T* dst = out + static_cast<size_t>(i) * channels * out_h * out_w;
      int h = 0, w = 0;
      if (decode_jpeg_raw(blob + offsets[i], sizes[i], channels, &pixels,
                          &h, &w)) {
        if (exact && (h != out_h || w != out_w)) {
          resample.store(true);
          return;
        }
        resize_to_chw(pixels.data(), h, w, channels, out_h, out_w, dst);
        ok.fetch_add(1);
      } else {
        std::memset(dst, 0, sizeof(T) * channels * out_h * out_w);
      }
    }
  });
  return resample.load() ? -1 : ok.load();
}

// cos_transform_batch's mean.  mode 0: none; 1: `c` values (one for all
// channels, or one each); 2: a plane of c x h x w (c as for mode 1).
struct Mean {
  const float* v;
  int mode, c, h, w;
};

// One image of cos_transform_batch: crop window (hs, ws) of a CHW source
// of type S, mirrored where `mir`, minus the mean, times scale, each
// output pixel written once.  The float operations and their order are
// those of data/transformer.py's Transformer.__call__ — convert,
// subtract, multiply — so the two agree bit for bit (v - 0.0f and
// v * 1.0f are v).
template <typename S>
void transform_image(const S* src, int c, int h, int w, int oh, int ow,
                     int hs, int ws, bool mir, const Mean& mean,
                     float scale, float* row, float* dst) {
  // a mean plane is indexed at the SOURCE pixel, before the mirror: a
  // full-size plane at the image's own crop window, any other at its
  // centre window of the output's size
  const bool full = mean.h == h && mean.w == w;
  const int my0 = full ? hs : (mean.h - oh) / 2;
  const int mx0 = full ? ws : (mean.w - ow) / 2;
  for (int ch = 0; ch < c; ++ch) {
    const int mch = mean.c > 1 ? ch : 0;
    const float mv = mean.mode == 1 ? mean.v[mch] : 0.0f;
    for (int y = 0; y < oh; ++y) {
      const S* srow = src + (static_cast<size_t>(ch) * h + hs + y) * w + ws;
      float* drow = dst + (static_cast<size_t>(ch) * oh + y) * ow;
      // a mirrored row is computed left to right into `row` (ow floats
      // of the worker's own) and stored reversed: both loops vectorise
      float* t = mir ? row : drow;
      if (mean.mode == 2) {
        const float* mrow =
            mean.v + (static_cast<size_t>(mch) * mean.h + my0 + y) * mean.w +
            mx0;
        for (int x = 0; x < ow; ++x)
          t[x] = (static_cast<float>(srow[x]) - mrow[x]) * scale;
      } else {
        for (int x = 0; x < ow; ++x)
          t[x] = (static_cast<float>(srow[x]) - mv) * scale;
      }
      if (mir) std::reverse_copy(t, t + ow, drow);
    }
  }
}

}  // namespace

extern "C" {

// Decode a batch of JPEGs into a preallocated (n, channels, out_h, out_w)
// float32 buffer (BGR planes).  offsets[i]/sizes[i] locate image i inside
// `blob`.  Returns the number of successfully decoded images; failed
// slots are zero-filled.
int cos_decode_batch(const unsigned char* blob, const long* offsets,
                     const long* sizes, int n, int channels, int out_h,
                     int out_w, float* out, int num_threads) {
  return decode_batch_impl(blob, offsets, sizes, n, channels, out_h,
                           out_w, out, num_threads);
}

// uint8 output variant: the pixels stay one byte each until the
// transform (cos_transform_batch on the host, or the device stage of
// COS_DEVICE_TRANSFORM).  With `exact` set, returns -1 as soon as an
// image is not out_h x out_w (see decode_batch_impl).
int cos_decode_batch_u8(const unsigned char* blob, const long* offsets,
                        const long* sizes, int n, int channels,
                        int out_h, int out_w, unsigned char* out,
                        int num_threads, int exact) {
  return decode_batch_impl(blob, offsets, sizes, n, channels, out_h,
                           out_w, out, num_threads, exact != 0);
}

// Caffe transform_param semantics, one pass from the records' pixels to
// the float batch:
//   out[i] = (mirror(crop(in[i]) - mean) - mean_value) * scale
// The source is an NCHW batch `in`, or, where `in_ptrs` is given, n
// separate CHW images (raw Datum payloads, read in place); its pixels
// are uint8 (`src_u8`) or float32.  h_off/w_off: per-image crop
// origins; mirror_flags: per-image 0/1.  mean, mean_mode, mean_c/h/w:
// struct Mean's fields.
void cos_transform_batch(const void* in, const void* const* in_ptrs,
                         int src_u8, int n, int c, int h, int w, int crop,
                         const int* h_off, const int* w_off,
                         const unsigned char* mirror_flags,
                         const float* mean, int mean_mode, int mean_c,
                         int mean_h, int mean_w, float scale, float* out,
                         int num_threads) {
  const int oh = crop > 0 ? crop : h;
  const int ow = crop > 0 ? crop : w;
  const size_t image = static_cast<size_t>(c) * h * w;
  const Mean m{mean, mean_mode, mean_c, mean_h, mean_w};
  std::atomic<int> next(0);
  run_workers(num_threads, n, [&]() {
    std::vector<float> row(ow);
    for (int i = next.fetch_add(1); i < n; i = next.fetch_add(1)) {
      const void* src =
          in_ptrs ? in_ptrs[i]
                  : static_cast<const char*>(in) +
                        i * image * (src_u8 ? 1 : sizeof(float));
      float* dst = out + static_cast<size_t>(i) * c * oh * ow;
      const int hs = crop > 0 ? h_off[i] : 0;
      const int ws = crop > 0 ? w_off[i] : 0;
      const bool mir = mirror_flags && mirror_flags[i];
      if (src_u8) {
        transform_image(static_cast<const unsigned char*>(src), c, h, w,
                        oh, ow, hs, ws, mir, m, scale, row.data(), dst);
      } else {
        transform_image(static_cast<const float*>(src), c, h, w, oh, ow,
                        hs, ws, mir, m, scale, row.data(), dst);
      }
    }
  });
}

// The device-transform split's host half, threaded: per-image crop
// window copy (+ optional horizontal mirror) on uint8 NCHW planes.
// h_off/w_off/mirror_flags are per-image (the Caffe RNG draws stay in
// Python so trajectories match the numpy path exactly); crop == 0
// means no crop (oh=h, ow=w).
void cos_crop_mirror_u8(const unsigned char* in, int n, int c, int h,
                        int w, int crop, const int* h_off,
                        const int* w_off,
                        const unsigned char* mirror_flags,
                        unsigned char* out, int num_threads) {
  const int oh = crop > 0 ? crop : h;
  const int ow = crop > 0 ? crop : w;
  std::atomic<int> next(0);
  run_workers(num_threads, n, [&]() {
    for (int i = next.fetch_add(1); i < n; i = next.fetch_add(1)) {
      const unsigned char* src =
          in + static_cast<size_t>(i) * c * h * w;
      unsigned char* dst =
          out + static_cast<size_t>(i) * c * oh * ow;
      // no-crop mode ignores the offsets (sibling cos_transform_batch
      // rule): a nonzero offset with oh==h would read out of bounds
      const int hs = crop > 0 ? h_off[i] : 0;
      const int ws = crop > 0 ? w_off[i] : 0;
      const bool mir = mirror_flags[i] != 0;
      for (int ch = 0; ch < c; ++ch) {
        const unsigned char* sp = src + static_cast<size_t>(ch) * h * w;
        unsigned char* dp = dst + static_cast<size_t>(ch) * oh * ow;
        for (int y = 0; y < oh; ++y) {
          const unsigned char* row = sp + (hs + y) * w + ws;
          unsigned char* orow = dp + y * ow;
          if (!mir) {
            std::memcpy(orow, row, ow);
          } else {
            for (int x = 0; x < ow; ++x) orow[x] = row[ow - 1 - x];
          }
        }
      }
    }
  });
}

int cos_native_version() { return 2; }

}  // extern "C"
