// Threaded prefetching batch loader.
//
// Worker threads claim batch indices in order, read + decode + resize each
// file, and pack a contiguous [batch, H, W, 3] uint8 buffer; finished
// batches sit in a bounded reorder window until the consumer pops them in
// sequence. This is the host half of the ingest path (SURVEY.md §7 phase
// 2): the Python side copies each batch into a persistent numpy staging
// buffer and jax.device_put's it, overlapping disk/decode with TPU compute —
// replacing the reference's per-element JNI copies (CNTKModel.scala:67-74)
// and scp/getmerge data movement (CommandBuilders.scala:200-228).

#include "mmltpu.h"

#include <condition_variable>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <map>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

namespace {

struct Batch {
  std::vector<uint8_t> data;  // batch*H*W*3, zero-filled padding/failures
  std::vector<uint8_t> ok;    // per-slot decode success
  int count = 0;              // valid rows (< batch only in the final batch)
};

struct Loader {
  std::vector<std::string> paths;
  int batch, out_h, out_w, n_batches, max_prefetch;
  std::vector<std::thread> workers;

  std::mutex mu;
  std::condition_variable cv_produced, cv_space;
  std::map<int, Batch> ready;   // reorder window keyed by batch index
  int next_claim = 0;           // next batch index a worker takes
  int next_emit = 0;            // next batch index the consumer needs
  bool stopping = false;

  size_t batch_bytes() const {
    return static_cast<size_t>(batch) * out_h * out_w * 3;
  }

  void fill_slot(const std::string &path, uint8_t *dst, uint8_t *ok) {
    *ok = 0;
    FILE *f = fopen(path.c_str(), "rb");
    if (!f) return;
    fseek(f, 0, SEEK_END);
    const long sz = ftell(f);
    fseek(f, 0, SEEK_SET);
    if (sz <= 0) { fclose(f); return; }
    std::vector<uint8_t> raw(static_cast<size_t>(sz));
    const size_t got = fread(raw.data(), 1, raw.size(), f);
    fclose(f);
    if (got != raw.size()) return;
    uint8_t *img = nullptr;
    int h, w, c;
    if (mmltpu_decode_image(raw.data(), raw.size(), &img, &h, &w, &c) != 0)
      return;
    if (h == out_h && w == out_w)
      memcpy(dst, img, static_cast<size_t>(out_h) * out_w * 3);
    else
      mmltpu_resize_bilinear(img, h, w, 3, dst, out_h, out_w);
    mmltpu_free(img);
    *ok = 1;
  }

  void work() {
    for (;;) {
      int bi;
      {
        std::unique_lock<std::mutex> lk(mu);
        // bound in-flight batches so memory stays O(prefetch window)
        cv_space.wait(lk, [&] {
          return stopping || (next_claim < n_batches &&
                              next_claim - next_emit < max_prefetch);
        });
        if (stopping || next_claim >= n_batches) return;
        bi = next_claim++;
      }
      Batch b;
      b.data.assign(batch_bytes(), 0);
      b.ok.assign(batch, 0);
      const int lo = bi * batch;
      const int hi = std::min<int>(lo + batch, paths.size());
      b.count = hi - lo;
      const size_t slot = static_cast<size_t>(out_h) * out_w * 3;
      for (int i = lo; i < hi; ++i)
        fill_slot(paths[i], b.data.data() + (i - lo) * slot, &b.ok[i - lo]);
      {
        std::lock_guard<std::mutex> lk(mu);
        if (stopping) return;
        ready.emplace(bi, std::move(b));
      }
      cv_produced.notify_all();
    }
  }
};

}  // namespace

extern "C" void *mmltpu_loader_create(const char *const *paths, int n_paths,
                                      int batch, int out_h, int out_w,
                                      int n_threads, int max_prefetch) {
  if (n_paths < 0 || batch <= 0 || out_h <= 0 || out_w <= 0) return nullptr;
  Loader *ld = new Loader();
  ld->paths.reserve(n_paths);
  for (int i = 0; i < n_paths; ++i) ld->paths.emplace_back(paths[i]);
  ld->batch = batch;
  ld->out_h = out_h;
  ld->out_w = out_w;
  ld->n_batches = (n_paths + batch - 1) / batch;
  const int nt = std::max(1, std::min(n_threads, ld->n_batches == 0 ? 1
                                                 : ld->n_batches));
  // workers claim whole batches, so in-flight window must cover the thread
  // pool or threads beyond the window would never run
  ld->max_prefetch = std::max(std::max(1, max_prefetch), nt);
  for (int i = 0; i < nt; ++i)
    ld->workers.emplace_back([ld] { ld->work(); });
  return ld;
}

extern "C" int mmltpu_loader_next(void *handle, uint8_t *out, uint8_t *ok,
                                  int *out_count) {
  Loader *ld = static_cast<Loader *>(handle);
  Batch b;
  {
    std::unique_lock<std::mutex> lk(ld->mu);
    if (ld->next_emit >= ld->n_batches) return 0;
    ld->cv_produced.wait(lk, [&] {
      return ld->ready.count(ld->next_emit) > 0;
    });
    auto it = ld->ready.find(ld->next_emit);
    b = std::move(it->second);
    ld->ready.erase(it);
    ld->next_emit++;
  }
  ld->cv_space.notify_all();  // window advanced: workers may claim again
  memcpy(out, b.data.data(), b.data.size());
  memcpy(ok, b.ok.data(), b.ok.size());
  *out_count = b.count;
  return 1;
}

extern "C" int mmltpu_loader_ready(void *handle) {
  Loader *ld = static_cast<Loader *>(handle);
  std::lock_guard<std::mutex> lk(ld->mu);
  return static_cast<int>(ld->ready.size());
}

extern "C" void mmltpu_loader_destroy(void *handle) {
  Loader *ld = static_cast<Loader *>(handle);
  {
    std::lock_guard<std::mutex> lk(ld->mu);
    ld->stopping = true;
  }
  ld->cv_space.notify_all();
  ld->cv_produced.notify_all();
  for (auto &t : ld->workers) t.join();
  delete ld;
}
