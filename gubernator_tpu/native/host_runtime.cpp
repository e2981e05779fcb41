// Native host runtime: key -> device-slot table + batch round planner.
//
// This is the C++ twin of models/slot_table.py (the reference's LRU
// cache role, cache.go:52-218) plus the round-planning loop of
// models/shard.py::RoundPlanner. The TPU kernel wants whole batches of
// unique (key, slot) lanes; the host must resolve string keys to dense
// slots, keep LRU order for eviction, mirror expiry (expiry == miss,
// cache.go:138-163), and split duplicate-key batches into sequential
// rounds (the vectorized equivalent of the reference's mutex
// serialization, gubernator.go:336-337). All of that is pure pointer
// chasing that Python does 50-100x slower than C++ — this module exists
// so the device kernel, not the host, is the bottleneck.
//
// Shape of the table (Table below): ONE 64-byte record a slot (SlotRec:
// key bytes, expiry, pending-write count, LRU links, mapped flag — what
// a lookup, a touch, a commit and an eviction read for a slot is one
// cache line) and ONE flat open-addressing index (KeyIndex: 8-byte
// entries of 32 hash bits + slot) keyed by the FNV-1a 64 of the key
// bytes that the mesh planner already computes for shard routing.  A
// hit on the hash bits is always confirmed against the record's own key
// bytes.  A frame's keys are resolved together (gt_batch_plan_grouped):
// index line and record of the keys ahead are prefetched while the
// current key's LRU touch runs, so the misses overlap instead of
// queueing behind one another.
//
// Exposed as a plain C ABI for ctypes (no pybind11 in this image).
// Thread-safety contract: each Table carries its own recursive mutex,
// taken by every extern-C entry that touches it.  It guards every
// member of the Table: the records, both indexes and their counters,
// the free list, the LRU ends, the move queues and the plan scratch
// (which is why a plan can keep its scratch on the table and reuse it).
// This is what lets the overlapped dispatch pipeline run batch N+1's
// PLANNING concurrently with batch N's in-flight DECODE/COMMIT
// (models/shard.py ColumnarPipeline): the two stages hold different
// Python locks, and ctypes releases the GIL for the call's duration, so
// without internal locking they would race on the same index.
// Interleaving at call granularity is safe by the same argument as
// pipelined planning itself — a plan that runs before an older batch's
// commit observes expiry lagging by the unresolved depth (revalidated
// device-side), and pending_write refcounts keep in-flight slots
// uneviction-able.  Cross-batch ORDERING is the Python tier's job
// (plan-order tickets + the FIFO drain); this mutex only makes each
// call atomic.

#include <algorithm>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <mutex>
#include <random>
#include <string>
#include <string_view>
#include <unordered_map>
#include <vector>

namespace {

// FNV-1 / FNV-1a 64: the shard-routing hash (replicated_hash.go:31).
// Single definitions shared by gt_fnv1_batch and the mesh planner so
// shard routing cannot diverge between the two.  The 1a value of a key
// is also what the slot table's index is keyed by (KeyIndex::mix).
inline uint64_t fnv1a64(const char* p, const char* end) {
  uint64_t h = 14695981039346656037ull;
  for (; p < end; ++p) {
    h ^= (uint64_t)(unsigned char)*p;
    h *= 1099511628211ull;
  }
  return h;
}

inline uint64_t fnv1_64(const char* p, const char* end) {
  uint64_t h = 14695981039346656037ull;
  for (; p < end; ++p) {
    h *= 1099511628211ull;
    h ^= (uint64_t)(unsigned char)*p;
  }
  return h;
}

// Everything the table keeps for one slot, in one cache line.  `hash`
// is the key's plain FNV-1a 64 (the index re-mixes it when an erase
// has to re-home a neighbour).  Keys up to kInline bytes live in the
// record; a longer key is one heap block the record owns.
struct alignas(64) SlotRec {
  static constexpr uint32_t kInline = 24;
  int64_t expire_ms = 0;
  uint64_t hash = 0;
  // LRU intrusive list over slots; -1 = null.
  int32_t lru_prev = -1, lru_next = -1;
  // In-flight (planned, not yet committed) device writes.  While >0 the
  // device row is fresher than expire_ms, so liveness is
  // device-authoritative — the pipelined twin of the planner's chained
  // lanes (see gt_batch_plan).  Nonzero only between a columnar batch's
  // plan and its commit.
  int32_t pending_write = 0;
  // Two-tier: index into mv_promo_* of a queued-but-undrained promotion
  // (-1 none).  The row is not on device yet, so eviction must prefer
  // other slots and, if forced, CANCEL the record.
  int32_t pending_promo = -1;
  uint32_t key_len = 0;
  uint8_t mapped = 0;  // 0 = free (no key)
  union {
    char inl[kInline];
    char* heap;
  } key;

  const char* key_ptr() const { return key_len <= kInline ? key.inl : key.heap; }
  bool key_is(const char* p, size_t len) const {
    return mapped && key_len == len && std::memcmp(key_ptr(), p, len) == 0;
  }
  void clear_key() {
    if (key_len > kInline) std::free(key.heap);
    key_len = 0;
  }
  void set_key(const char* p, size_t len) {
    clear_key();
    if (len > kInline) {
      key.heap = (char*)std::malloc(len);
      std::memcpy(key.heap, p, len);
    } else {
      std::memcpy(key.inl, p, len);
    }
    key_len = (uint32_t)len;
  }
};
static_assert(sizeof(SlotRec) == 64, "one record a cache line");

// Open-addressing index key -> slot: linear probing over 8-byte entries
// ((32 hash bits) << 32 | slot + 1; 0 = empty), at most half full, and
// erased by shifting the chain back (no tombstones, so a chain's length
// is a function of what is resident, not of history).  The index knows
// no key bytes: a caller confirms a hash-bit hit against the slot's own
// key (`eq`), always, so two keys that agree in all 64 bits are still
// two entries.  The position comes from the low bits of the mixed hash
// and the stored bits from the high 32, so the two are independent.
struct KeyIndex {
  std::vector<uint64_t> ent;
  uint64_t mask = 0;
  // Drawn at start: where a key lands is not a constant of the build.
  uint64_t seed = 0;
  // Test-only (gt_table_new_hashed): force keys onto the same hash bits.
  uint64_t h_and = ~0ull, h_or = 0;
  // Health, served a shard by occupancy_stats(): resolutions, entries
  // inspected for them, and hash-bit hits the key compare refused.
  int64_t lookups = 0, probes = 0, refused = 0;

  void init(int64_t cap) {
    size_t n = 8;
    while (n < (size_t)cap * 2) n <<= 1;
    ent.assign(n, 0);
    mask = n - 1;
    std::random_device rd;
    seed = ((uint64_t)rd() << 32) | rd();
  }

  uint64_t mix(uint64_t h) const {
    h ^= seed;
    h *= 0xff51afd7ed558ccdull;
    h ^= h >> 33;
    h *= 0xc4ceb9fe1a85ec53ull;
    h ^= h >> 33;
    return (h & h_and) | h_or;
  }

  static uint64_t entry(uint64_t mh, int32_t slot) {
    return (mh & 0xffffffff00000000ull) | (uint32_t)(slot + 1);
  }
  static int32_t slot_of(uint64_t e) { return (int32_t)(uint32_t)e - 1; }

  void prefetch(uint64_t mh) const { __builtin_prefetch(&ent[mh & mask]); }

  // The slot of the first entry of mh's chain with mh's hash bits, or
  // -1: the batched pass's probe (the caller confirms the key later,
  // when the record it prefetches has arrived, and walks on refusal).
  int32_t probe_bits(uint64_t mh) {
    ++lookups;
    for (uint64_t p = mh & mask;; p = (p + 1) & mask) {
      ++probes;
      uint64_t e = ent[p];
      if (e == 0) return -1;
      if ((e ^ mh) >> 32 == 0) return slot_of(e);
    }
  }

  // Walk mh's chain for the entry whose slot `eq` accepts; -1 if none.
  template <class Eq>
  int32_t walk(uint64_t mh, Eq eq) {
    for (uint64_t p = mh & mask;; p = (p + 1) & mask) {
      ++probes;
      uint64_t e = ent[p];
      if (e == 0) return -1;
      if ((e ^ mh) >> 32 != 0) continue;
      int32_t s = slot_of(e);
      if (eq(s)) return s;
      ++refused;
    }
  }

  template <class Eq>
  int32_t find(uint64_t mh, Eq eq) {
    ++lookups;
    return walk(mh, eq);
  }

  // The caller has established that the key is absent.
  void insert(uint64_t mh, int32_t slot) {
    uint64_t p = mh & mask;
    while (ent[p] != 0) p = (p + 1) & mask;
    ent[p] = entry(mh, slot);
  }

  // Remove `slot`'s entry and close the gap: each later entry of the
  // run moves back unless that would put it before its home position
  // (`mixed_of(slot)` gives a resident slot's mixed hash).
  template <class MixedOf>
  void erase(uint64_t mh, int32_t slot, MixedOf mixed_of) {
    uint64_t want = entry(mh, slot);
    uint64_t i = mh & mask;
    while (ent[i] != want) {
      if (ent[i] == 0) return;  // not indexed: nothing to close
      i = (i + 1) & mask;
    }
    for (uint64_t j = (i + 1) & mask; ent[j] != 0; j = (j + 1) & mask) {
      uint64_t home = mixed_of(slot_of(ent[j])) & mask;
      if (((j - home) & mask) >= ((j - i) & mask)) {
        ent[i] = ent[j];
        i = j;
      }
    }
    ent[i] = 0;
  }
};

struct Table {
  // Guards every member below against concurrent extern-C calls
  // (recursive: gt_mesh_* entries call gt_batch_* entries on the same
  // table).  See the thread-safety contract at the top of the file.
  std::recursive_mutex mu;
  int64_t capacity;
  std::vector<SlotRec> recs;  // slot -> its record (mapped = 0 when free)
  KeyIndex index;             // key -> front slot
  int64_t size = 0;           // mapped front slots
  int32_t lru_head = -1, lru_tail = -1;  // head = least recent
  std::vector<int32_t> free_slots;  // stack, top = back
  // `evictions` counts buckets that LEFT the table for want of room
  // (both tiers: a demotion keeps the bucket and is not one);
  // `front_evictions` counts every front slot taken from its key, kept
  // or not: the planners' signal that a lookup stole a slot.
  int64_t hits = 0, misses = 0, evictions = 0, front_evictions = 0;
  // Bumped on every key->front-slot MAPPING change (assign, remap,
  // evict, remove).  NOT bumped by in-place expiry reuse (same key,
  // same slot) or value/expire writes.  Lets the GLOBAL sync skip
  // owner-slot re-verification for shards whose mapping is provably
  // unchanged since the last sync (O(active-gslots) -> O(changed)).
  uint64_t map_generation = 0;

  // ---- two-tier mode (back_capacity > 0) ----------------------------
  // The device keeps a small FRONT table (every kernel lane addresses
  // it — random-row scatter cost scales with table size, measured
  // ~2.4ns/slot on TPU v5e) plus a big BACK table written only by
  // batched demotion scatters.  Front LRU eviction DEMOTES the row
  // (device move, state preserved) instead of dropping it; a later
  // lookup PROMOTES it back (cheap device gather).  The host tracks
  // key locations and queues the device moves; dispatchers drain them
  // (gt_table_take_moves -> ops/buckets.apply_moves) before any
  // program that reads front rows.  A back slot that a promotion, an
  // expiry or a removal freed is taken before the ring's next; only
  // with no free slot does the back tier evict, the slot under the
  // ring cursor: by ring position, NOT by age (a refilled slot keeps
  // its place in the ring, so the victim may be the newest demotion,
  // and a back row that has expired is freed only when it is looked
  // up, so a live bucket can go while dead rows hold slots) — then
  // alone is bucket state truly lost, so a population that fits front
  // + back loses nothing, like the reference's LRU at a capacity above
  // it.
  int64_t back_capacity = 0;
  KeyIndex back_index;                // key -> back slot (same index type)
  std::vector<std::string> back_key;  // back slot -> key
  std::vector<uint64_t> back_hash;    // ... and its plain FNV-1a 64
  std::vector<uint8_t> back_mapped;
  std::vector<int64_t> back_expire;
  int64_t back_clock = 0;  // FIFO allocation cursor
  std::vector<int32_t> back_free;  // freed back slots (stack); never holds a mapped one
  int64_t back_size = 0, back_evictions = 0, demotions = 0, promotions = 0;
  // Pending device moves.  promo kind: 0 = gather from back slot, 1 =
  // gather from FRONT slot (a key demoted and re-promoted inside one
  // drain window — its row never reached the back table, so the
  // device copies front->front; the demo record still parks the stale
  // copy in the back slot, which the host no longer maps).
  std::vector<int32_t> mv_promo_kind, mv_promo_src, mv_promo_dst;
  std::vector<int32_t> mv_demo_src, mv_demo_dst;
  // back slot -> index into mv_demo (this window) for cycle rewrite
  std::unordered_map<int32_t, int32_t> pending_demo_by_back;

  // ---- grouped-plan scratch (gt_batch_plan_grouped) -----------------
  // Kept here and reused so a dispatch allocates nothing a lane: the
  // flat group table over the frame's 64-bit hashes, the CSR of groups,
  // and the per-group probe results of the prefetched pass.
  struct PlanScratch {
    std::vector<int32_t> gtab, gid, gfirst, gcount, goff, gmembers, cursor,
        cand, slow, r0;
    std::vector<uint64_t> mh;
  } scratch;

  explicit Table(int64_t cap) : capacity(cap), recs((size_t)cap) {
    index.init(cap);
    free_slots.reserve(cap);
    for (int64_t i = cap - 1; i >= 0; --i) free_slots.push_back((int32_t)i);
  }

  ~Table() {
    for (SlotRec& r : recs) r.clear_key();
  }

  uint64_t mixed_of_slot(int32_t s) const { return index.mix(recs[s].hash); }

  void index_erase(int32_t s) {
    index.erase(mixed_of_slot(s), s,
                [this](int32_t o) { return mixed_of_slot(o); });
  }

  int32_t find_slot(const char* key, size_t len, uint64_t h) {
    return index.find(index.mix(h), [&](int32_t s) {
      return recs[s].key_is(key, len);
    });
  }

  void lru_unlink(int32_t s) {
    SlotRec& r = recs[s];
    int32_t p = r.lru_prev, n = r.lru_next;
    if (p >= 0) recs[p].lru_next = n; else if (lru_head == s) lru_head = n;
    if (n >= 0) recs[n].lru_prev = p; else if (lru_tail == s) lru_tail = p;
    r.lru_prev = r.lru_next = -1;
  }

  void lru_push_back(int32_t s) {  // most recently used
    recs[s].lru_prev = lru_tail;
    recs[s].lru_next = -1;
    if (lru_tail >= 0) recs[lru_tail].lru_next = s;
    lru_tail = s;
    if (lru_head < 0) lru_head = s;
  }

  void touch(int32_t s) {
    if (lru_tail == s) return;
    lru_unlink(s);
    lru_push_back(s);
  }

  void unmap_slot(int32_t s) {
    SlotRec& r = recs[s];
    if (!r.mapped) return;
    index_erase(s);
    r.clear_key();
    r.mapped = 0;
    r.expire_ms = 0;
    --size;
    lru_unlink(s);
    free_slots.push_back(s);
    ++map_generation;
  }

  void enable_back(int64_t cap) {
    back_capacity = cap;
    back_index.init(cap);
    back_index.h_and = index.h_and;
    back_index.h_or = index.h_or;
    back_key.resize(cap);
    back_hash.assign(cap, 0);
    back_mapped.assign(cap, 0);
    back_expire.assign(cap, 0);
  }

  int32_t find_back(const char* key, size_t len, uint64_t h) {
    return back_index.find(back_index.mix(h), [&](int32_t b) {
      return back_mapped[b] && back_key[b].size() == len &&
             std::memcmp(back_key[b].data(), key, len) == 0;
    });
  }

  // Take back slot b's key off it; the slot is the caller's to reuse.
  void unlink_back(int32_t b) {
    back_index.erase(back_index.mix(back_hash[b]), b, [this](int32_t o) {
      return back_index.mix(back_hash[o]);
    });
    back_key[b].clear();
    back_mapped[b] = 0;
    back_expire[b] = 0;
    --back_size;
  }

  void unmap_back(int32_t b) {
    if (!back_mapped[b]) return;
    unlink_back(b);
    back_free.push_back(b);
  }

  // Neutralize a queued demo targeting back slot b (src=-1 device
  // no-op): required whenever b is freed or reused mid-window, or the
  // move program could scatter two rows onto one destination.
  void cancel_pending_demo(int32_t b) {
    auto pd = pending_demo_by_back.find(b);
    if (pd != pending_demo_by_back.end()) {
      mv_demo_src[(size_t)pd->second] = -1;
      pending_demo_by_back.erase(pd);
    }
  }

  // A back slot mid-promotion: assign() resolves the promo source
  // BEFORE allocating the front slot, and that allocation's eviction
  // can demote another key.  The source stays mapped (and so off the
  // free list) until the promotion is queued, and the ring skips it:
  // handing it over by eviction gave the promoted key the victim's
  // row (found by round-4 review, repro'd with front=1/back=1).
  int32_t promo_in_flight = -1;

  // A back slot for a demoted key, in this order: the ring's next
  // while the ring is on its first lap (a slot never used); one that a
  // promotion, an expiry or a removal freed; with a promotion in flight
  // and the back tier full, that promotion's own source (a swap: the
  // move program reads the promoted row and writes the demoted one in
  // one window, and assign() has taken what it needs of the source's
  // record by then); last, the ring's next though it is live, which
  // drops that bucket — the two-tier design's only true state loss,
  // and only with front and back both full.  Returns -1 when no slot
  // is usable (back_capacity==1 and that slot is mid-promotion: a
  // one-slot back tier keeps its documented behaviour, the caller
  // drops the row instead of demoting).
  int32_t alloc_back(const char* key, size_t len, uint64_t h) {
    int32_t b;
    if (back_clock < back_capacity) {
      b = (int32_t)back_clock++;
    } else if (!back_free.empty()) {
      b = back_free.back();
      back_free.pop_back();
    } else if (promo_in_flight >= 0) {
      if (back_capacity == 1) return -1;
      b = promo_in_flight;  // the swap; the ring stays where it is
      unlink_back(b);
      promo_in_flight = -1;
    } else {
      b = (int32_t)(back_clock++ % back_capacity);
      unlink_back(b);  // live: with no slot free every slot is
      ++back_evictions;
      ++evictions;
    }
    cancel_pending_demo(b);
    back_key[b].assign(key, len);
    back_hash[b] = h;
    back_mapped[b] = 1;
    back_index.insert(back_index.mix(h), b);
    ++back_size;
    return b;
  }

  // Demote the (still-live) key occupying front slot s: queue the
  // device row move front[s] -> back[b] and move the host mapping.
  // Expired occupants are simply dropped — dead state is not worth a
  // back slot.
  void evict_front(int32_t s, int64_t now_ms) {
    lru_unlink(s);
    SlotRec& r = recs[s];
    index_erase(s);
    r.mapped = 0;
    --size;
    // Demotion preserves state ONLY when the device row at s really is
    // this key's current state.  Under the all-pending starvation
    // fallback the chosen slot may have (a) a queued promotion whose
    // row hasn't arrived — demoting would park the PREVIOUS occupant's
    // row under this key's name (cross-key corruption, round-4 review
    // repro) — cancel the promo and drop instead; (b) an in-flight
    // batch write (pending_write) — the row is mid-air, drop.  Both
    // degrade to the documented reference-grade loss, never to serving
    // another key's counters.
    bool kept = false;
    if (r.pending_promo >= 0) {
      mv_promo_src[(size_t)r.pending_promo] = -1;  // device no-op
      r.pending_promo = -1;
      ++back_evictions;  // the promoted state is lost
    } else if (back_capacity > 0 && r.pending_write == 0 &&
               r.expire_ms >= now_ms) {
      int32_t b = alloc_back(r.key_ptr(), r.key_len, r.hash);
      if (b >= 0) {
        back_expire[b] = r.expire_ms;
        pending_demo_by_back[b] = (int32_t)mv_demo_src.size();
        mv_demo_src.push_back(s);
        mv_demo_dst.push_back(b);
        ++demotions;
        kept = true;
      } else {
        ++back_evictions;  // degenerate: nowhere to park the row
      }
    }
    r.clear_key();
    r.expire_ms = 0;
    if (!kept) ++evictions;  // a demoted bucket is still resident
    ++front_evictions;
    ++map_generation;
  }

  // Give free slot s to `key` (hash h) as the most recently used.
  void map_slot(int32_t s, const char* key, size_t len, uint64_t h) {
    SlotRec& r = recs[s];
    r.set_key(key, len);
    r.hash = h;
    r.mapped = 1;
    ++size;
    index.insert(index.mix(h), s);
    lru_push_back(s);
    ++map_generation;
  }

  // Re-map an unmapped slot to `key` (the remove-then-recreate chain:
  // an earlier lane freed the slot, a later round recreated the key on
  // device).  Returns false when the key is meanwhile mapped elsewhere.
  // Negative expire is the narrow-wire keep-sentinel; an unmapped slot
  // has no prior value to keep, so it clamps to 0 (already expired).
  bool remap(int32_t s, const char* key, size_t len, uint64_t h,
             int64_t expire) {
    if (find_slot(key, len, h) >= 0) return false;
    for (size_t j = free_slots.size(); j > 0; --j) {
      if (free_slots[j - 1] == s) {
        free_slots[j - 1] = free_slots.back();
        free_slots.pop_back();
        break;
      }
    }
    map_slot(s, key, len, h);
    recs[s].expire_ms = expire >= 0 ? expire : 0;
    return true;
  }

  // A lookup that found the key at front slot s.
  std::pair<int32_t, bool> hit(int32_t s, int64_t now_ms) {
    touch(s);
    // Strict expiry (cache.go:151); an uncommitted in-flight write
    // makes the device row authoritative regardless of the stale
    // host expire (pipelined batches — the kernel revalidates).
    if (recs[s].expire_ms >= now_ms || recs[s].pending_write > 0) {
      ++hits;
      return {s, true};
    }
    ++misses;  // expired: recycle same slot in place
    return {s, false};
  }

  // A lookup that found the key in no front slot: promotion from the
  // back tier, a free slot, or an eviction.  Order-dependent (which
  // slot is free, who is the LRU victim), so a frame's misses take this
  // path one at a time, in request order.
  std::pair<int32_t, bool> assign(const char* key, size_t len, uint64_t h,
                                  int64_t now_ms) {
    // Two-tier: a live row demoted to the back tier promotes (a
    // logical cache hit — the state survives the round trip).
    // What the promotion needs of its source's record is taken here,
    // before the front slot is allocated: that allocation's eviction
    // may hand the source slot itself to the demoted key (alloc_back's
    // swap).  A demo still pending for the source (same drain window)
    // means the row never left the front table — the device copies
    // front->front (kind 1) instead of reading the not-yet-written
    // back slot, and the parked demo copy is cancelled (its
    // destination is free for same-window reuse).
    int32_t promo_b = -1, promo_kind = 0, promo_src = -1;
    int64_t promo_expire = 0;
    if (back_capacity > 0) {
      int32_t b = find_back(key, len, h);
      if (b >= 0) {
        if (back_expire[b] >= now_ms) {
          promo_b = promo_src = b;
          promo_expire = back_expire[b];
          auto pd = pending_demo_by_back.find(b);
          if (pd != pending_demo_by_back.end()) {
            promo_kind = 1;
            promo_src = mv_demo_src[(size_t)pd->second];
            mv_demo_src[(size_t)pd->second] = -1;
            pending_demo_by_back.erase(pd);
          }
        } else {
          cancel_pending_demo(b);
          unmap_back(b);  // expired in back: plain miss-create
        }
      }
    }
    if (promo_b >= 0) ++hits; else ++misses;
    promo_in_flight = promo_b;  // shield the source from reuse by eviction
    int32_t s;
    if (!free_slots.empty()) {
      s = free_slots.back();
      free_slots.pop_back();
    } else {
      // Evict LRU (cache.go:115-130), skipping slots whose device write
      // from an earlier pipelined batch is still in flight — stealing
      // one drops that batch's device state mid-air and invalidates its
      // plan-time chaining assumptions — and slots awaiting a queued
      // promotion this drain window (their device row lands with the
      // NEXT move program; demoting one would copy a pre-promotion
      // row).  Walk from the cold end; under pipelining the pending
      // slots are the recently-touched ones, so the head is normally
      // clean.  Fall back to the raw head only when every slot is
      // pending (capacity fully in flight).
      // Preference ladder: fully clean slot > promo-free slot (in-
      // flight write: evict_front drops instead of demoting) > raw
      // head (pending promo: evict_front cancels the record — loss,
      // never corruption).
      s = -1;
      for (int32_t cand = lru_head; cand >= 0; cand = recs[cand].lru_next) {
        if (recs[cand].pending_write == 0 && recs[cand].pending_promo < 0) {
          s = cand;
          break;
        }
      }
      if (s < 0) {
        for (int32_t cand = lru_head; cand >= 0; cand = recs[cand].lru_next) {
          if (recs[cand].pending_promo < 0) {
            s = cand;
            break;
          }
        }
      }
      if (s < 0) s = lru_head;
      evict_front(s, now_ms);
    }
    map_slot(s, key, len, h);
    if (promo_b >= 0) {
      recs[s].expire_ms = promo_expire;
      mv_promo_kind.push_back(promo_kind);
      mv_promo_src.push_back(promo_src);
      mv_promo_dst.push_back(s);
      recs[s].pending_promo = (int32_t)mv_promo_dst.size() - 1;
      if (promo_in_flight >= 0) unmap_back(promo_in_flight);  // not swapped away: free
      promo_in_flight = -1;
      ++promotions;
      return {s, true};
    }
    promo_in_flight = -1;
    recs[s].expire_ms = 0;
    return {s, false};
  }

  // (slot, exists): exists=false means kernel treats as fresh create.
  // Mirrors slot_table.py::lookup_or_assign, except for pipelining
  // state the Python twin does not model: pending_write liveness and
  // pending-aware eviction only matter between a columnar batch's plan
  // and commit, and the pipelined path requires the native runtime —
  // the Python twin never observes in-flight writes, so the twins agree
  // on every state the Python table can reach.  `h` is the key's plain
  // FNV-1a 64.
  std::pair<int32_t, bool> lookup_or_assign(const char* key, size_t len,
                                            uint64_t h, int64_t now_ms) {
    int32_t s = find_slot(key, len, h);
    return s >= 0 ? hit(s, now_ms) : assign(key, len, h, now_ms);
  }
};

struct Batch {
  Table* table;
  const char* keys;        // concatenated key bytes (borrowed)
  const int64_t* offsets;  // n+1 offsets into keys (borrowed)
  // FNV-1a 64 of every key: the mesh planner's (borrowed; it hashed
  // them for shard routing) or, from gt_batch_begin, the batch's own.
  const uint64_t* hashes;
  std::vector<uint64_t> own_hashes;
  int64_t n;
  int64_t now_ms;
  // Lanes not yet scheduled, in request order (per-key order is what
  // matters; cross-key order is free, as in the reference's goroutine
  // fan-out).
  std::vector<int32_t> pending;
  // per-lane resolution cache (a deferred lane keeps its captured slot)
  std::vector<int32_t> slot;
  std::vector<uint8_t> exists, resolved;
  bool committed = false;
  // last emitted round
  std::vector<int32_t> round_lane;
  // full-plan mode (gt_batch_plan): lanes in emission order across all
  // rounds, consumed by gt_batch_commit_plan
  std::vector<int32_t> plan_order;

  Batch(Table* t, const char* k, const int64_t* off, int64_t n_, int64_t now,
        const uint64_t* hs)
      : table(t), keys(k), offsets(off), hashes(hs), n(n_), now_ms(now),
        slot(n_, -1), exists(n_, 0), resolved(n_, 0) {
    if (hashes == nullptr) {
      own_hashes.resize((size_t)n_);
      for (int64_t i = 0; i < n_; ++i)
        own_hashes[(size_t)i] = fnv1a64(k + off[i], k + off[i + 1]);
      hashes = own_hashes.data();
    }
    pending.reserve(n_);
    for (int64_t i = 0; i < n_; ++i) pending.push_back((int32_t)i);
  }

  const char* key_ptr(int64_t i) const { return keys + offsets[i]; }
  size_t key_len(int64_t i) const { return (size_t)(offsets[i + 1] - offsets[i]); }
  std::string_view key_view(int64_t i) const { return {key_ptr(i), key_len(i)}; }

  void resolve(int64_t i) {
    auto [s, e] = table->lookup_or_assign(key_ptr(i), key_len(i), hashes[i], now_ms);
    slot[i] = s;
    exists[i] = e ? 1 : 0;
  }
  // Does front slot s still map lane i's key?
  bool owns(int64_t i, int32_t s) const {
    return table->recs[s].key_is(key_ptr(i), key_len(i));
  }
};

// Per-table lock for the extern-C surface (see the thread-safety
// contract at the top of the file).
#define GT_LOCK(tp) std::lock_guard<std::recursive_mutex> _gt_guard((tp)->mu)

}  // namespace

extern "C" {

void* gt_table_new(int64_t capacity) { return new Table(capacity); }

// Test-only: a table whose index keeps `h_and` of a key's mixed hash
// and sets `h_or` (0, 0: every key on the same hash bits; ~7, 7: every
// chain starts at position 7).  The daemon never calls it.
void* gt_table_new_hashed(int64_t capacity, uint64_t h_and, uint64_t h_or) {
  Table* t = new Table(capacity);
  t->index.h_and = h_and;
  t->index.h_or = h_or;
  return t;
}

void gt_table_free(void* t) { delete (Table*)t; }
int64_t gt_table_len(void* t) {
  GT_LOCK((Table*)t);
  return ((Table*)t)->size;
}

void gt_table_stats(void* tv, int64_t* out) {  // hits, misses, evictions
  Table* t = (Table*)tv;
  GT_LOCK(t);
  out[0] = t->hits; out[1] = t->misses; out[2] = t->evictions;
}

// The front index's health: lookups, probes (entries inspected for
// them), hash-bit hits the key compare refused, entries allocated.
void gt_table_index_stats(void* tv, int64_t* out) {
  Table* t = (Table*)tv;
  GT_LOCK(t);
  out[0] = t->index.lookups; out[1] = t->index.probes;
  out[2] = t->index.refused; out[3] = (int64_t)t->index.ent.size();
}

// Buckets that left the table for want of room (Table::evictions).
int64_t gt_table_evictions(void* tv) {
  GT_LOCK((Table*)tv);
  return ((Table*)tv)->evictions;
}

// Front slots taken from their keys, demoted or dropped alike
// (Table::front_evictions): plan_grouped_python polls it around every
// lookup to learn that the lookup stole a slot.
int64_t gt_table_front_evictions(void* tv) {
  GT_LOCK((Table*)tv);
  return ((Table*)tv)->front_evictions;
}

// Mapping-change generation (see Table::map_generation): equal reads
// across two points in time guarantee no key->front-slot mapping
// changed between them.
uint64_t gt_table_generation(void* tv) {
  GT_LOCK((Table*)tv);
  return ((Table*)tv)->map_generation;
}

int32_t gt_table_get_slot(void* tv, const char* key, int64_t len) {
  Table* t = (Table*)tv;
  GT_LOCK(t);
  return t->find_slot(key, (size_t)len, fnv1a64(key, key + len));
}

// Single-key resolve (Store-SPI path drives lookups one at a time).
void gt_table_lookup_or_assign(void* tv, const char* key, int64_t len,
                               int64_t now_ms, int32_t* out_slot,
                               uint8_t* out_exists) {
  GT_LOCK((Table*)tv);
  auto [s, e] = ((Table*)tv)->lookup_or_assign(
      key, (size_t)len, fnv1a64(key, key + len), now_ms);
  *out_slot = s;
  *out_exists = e ? 1 : 0;
}

void gt_table_remove(void* tv, const char* key, int64_t len) {
  Table* t = (Table*)tv;
  GT_LOCK(t);
  uint64_t h = fnv1a64(key, key + len);
  int32_t s = t->find_slot(key, (size_t)len, h);
  if (s >= 0) t->unmap_slot(s);
  if (t->back_capacity > 0) {
    int32_t b = t->find_back(key, (size_t)len, h);
    if (b >= 0) {
      t->cancel_pending_demo(b);
      t->unmap_back(b);
    }
  }
}

// ---- two-tier back tier -----------------------------------------------

void gt_table_enable_back(void* tv, int64_t back_capacity) {
  GT_LOCK((Table*)tv);
  ((Table*)tv)->enable_back(back_capacity);
}

// out: total keys (front+back), back keys, demotions, promotions,
// back evictions (true state loss)
void gt_table_tier_stats(void* tv, int64_t* out) {
  Table* t = (Table*)tv;
  GT_LOCK(t);
  out[0] = t->size + t->back_size;
  out[1] = t->back_size;
  out[2] = t->demotions;
  out[3] = t->promotions;
  out[4] = t->back_evictions;
}

void gt_table_move_counts(void* tv, int64_t* n_promo, int64_t* n_demo) {
  Table* t = (Table*)tv;
  GT_LOCK(t);
  *n_promo = (int64_t)t->mv_promo_src.size();
  *n_demo = (int64_t)t->mv_demo_src.size();
}

// Drain the queued device moves into ONE caller block of 5 rows of
// `stride` int32 each (promo kind, promo src, promo dst, demo src, demo
// dst; the caller has filled it with its padding: src = -1) and close
// the drain window: after this call the rows are considered ON DEVICE
// in their new homes, so the dispatcher MUST run the move program
// (ops/buckets.apply_moves) with exactly these records before any
// other device program.  counts = (promotions, demotions) queued.
// Returns 0 when done; 1, with nothing drained, when either count is
// over `stride` (a planner queued more since the caller sized the
// block: it sizes again).
int32_t gt_table_take_moves(void* tv, int32_t* block, int64_t stride,
                            int64_t* counts) {
  Table* t = (Table*)tv;
  GT_LOCK(t);
  const size_t n_promo = t->mv_promo_src.size();
  const size_t n_demo = t->mv_demo_src.size();
  counts[0] = (int64_t)n_promo;
  counts[1] = (int64_t)n_demo;
  if ((int64_t)n_promo > stride || (int64_t)n_demo > stride) return 1;
  const std::vector<int32_t>* rows[5] = {
      &t->mv_promo_kind, &t->mv_promo_src, &t->mv_promo_dst,
      &t->mv_demo_src, &t->mv_demo_dst};
  for (int r = 0; r < 5; ++r)
    std::memcpy(block + r * stride, rows[r]->data(),
                rows[r]->size() * sizeof(int32_t));
  for (int32_t s : t->mv_promo_dst) t->recs[s].pending_promo = -1;
  t->mv_promo_kind.clear();
  t->mv_promo_src.clear();
  t->mv_promo_dst.clear();
  t->mv_demo_src.clear();
  t->mv_demo_dst.clear();
  t->pending_demo_by_back.clear();
  return 0;
}

// Snapshot protocol for the back tier (Loader.Save needs every live
// item): gt_table_back_size for buffer sizing, then gt_table_back_keys
// fills (back_slots, expire, offsets[count+1], key bytes), in back-slot
// order.
void gt_table_back_size(void* tv, int64_t* count, int64_t* total_bytes) {
  Table* t = (Table*)tv;
  GT_LOCK(t);
  *count = t->back_size;
  int64_t bytes = 0;
  for (int64_t b = 0; b < t->back_capacity; ++b)
    if (t->back_mapped[b]) bytes += (int64_t)t->back_key[b].size();
  *total_bytes = bytes;
}

void gt_table_back_keys(void* tv, int32_t* slots, int64_t* expire,
                        int64_t* offsets, char* bytes) {
  Table* t = (Table*)tv;
  GT_LOCK(t);
  int64_t i = 0, off = 0;
  for (int64_t b = 0; b < t->back_capacity; ++b) {
    if (!t->back_mapped[b]) continue;
    slots[i] = (int32_t)b;
    expire[i] = t->back_expire[b];
    offsets[i] = off;
    std::memcpy(bytes + off, t->back_key[b].data(), t->back_key[b].size());
    off += (int64_t)t->back_key[b].size();
    ++i;
  }
  offsets[i] = off;
}

void gt_table_set_expire(void* tv, int32_t slot, int64_t expire) {
  GT_LOCK((Table*)tv);
  ((Table*)tv)->recs[slot].expire_ms = expire;
}

// Bulk expiry read for the narrow-wire keep-sentinel decode: lanes
// whose expire/reset passed through unchanged reconstruct the absolute
// value from the host table instead of a (clippable) delta.
void gt_table_get_expire(void* tv, const int32_t* slots, int64_t n,
                         int64_t* out) {
  Table* t = (Table*)tv;
  GT_LOCK(t);
  for (int64_t i = 0; i < n; ++i)
    out[i] = (slots[i] >= 0 && slots[i] < t->capacity) ? t->recs[slots[i]].expire_ms : 0;
}

// Fold kernel outputs back (slot_table.py::commit): slots<0 skipped.
void gt_table_commit(void* tv, const int32_t* slots, const int64_t* expire,
                     const uint8_t* removed, int64_t n) {
  Table* t = (Table*)tv;
  GT_LOCK(t);
  for (int64_t i = 0; i < n; ++i) {
    int32_t s = slots[i];
    if (s < 0) continue;
    if (removed[i]) t->unmap_slot(s);
    else t->recs[s].expire_ms = expire[i];
  }
}

// Commit with the staleness guard (slot_table.py::commit keys check): a
// lane whose slot was remapped to a different key after scheduling (LRU
// eviction mid-batch) must not touch the slot's new owner. Used by the
// Python round loop (Store-SPI path); the planner path enforces this
// per-round in gt_batch_commit_round.
void gt_table_commit_keys(void* tv, const int32_t* slots,
                          const int64_t* expire, const uint8_t* removed,
                          const char* keys, const int64_t* offsets,
                          int64_t n) {
  Table* t = (Table*)tv;
  GT_LOCK(t);
  for (int64_t i = 0; i < n; ++i) {
    int32_t s = slots[i];
    if (s < 0) continue;
    const char* key = keys + offsets[i];
    size_t len = (size_t)(offsets[i + 1] - offsets[i]);
    SlotRec& r = t->recs[s];
    if (!r.mapped) {
      if (!removed[i]) t->remap(s, key, len, fnv1a64(key, key + len), expire[i]);
      continue;
    }
    if (!r.key_is(key, len))
      continue;  // slot remapped mid-batch; this lane is stale
    if (removed[i]) t->unmap_slot(s);
    else r.expire_ms = expire[i];
  }
}

// Snapshot protocol: first call gt_table_keys_size for total bytes, then
// gt_table_keys to fill (slots, offsets[count+1], bytes), in slot order.
void gt_table_keys_size(void* tv, int64_t* count, int64_t* total_bytes) {
  Table* t = (Table*)tv;
  GT_LOCK(t);
  *count = t->size;
  int64_t bytes = 0;
  for (const SlotRec& r : t->recs)
    if (r.mapped) bytes += (int64_t)r.key_len;
  *total_bytes = bytes;
}

void gt_table_keys(void* tv, int32_t* slots, int64_t* offsets, char* bytes) {
  Table* t = (Table*)tv;
  GT_LOCK(t);
  int64_t i = 0, off = 0;
  for (int64_t s = 0; s < t->capacity; ++s) {
    const SlotRec& r = t->recs[(size_t)s];
    if (!r.mapped) continue;
    slots[i] = (int32_t)s;
    offsets[i] = off;
    std::memcpy(bytes + off, r.key_ptr(), r.key_len);
    off += (int64_t)r.key_len;
    ++i;
  }
  offsets[i] = off;
}

void* gt_batch_begin(void* tv, const char* keys, const int64_t* offsets,
                     int64_t n, int64_t now_ms) {
  return new Batch((Table*)tv, keys, offsets, n, now_ms, nullptr);
}

// Emit the next round: walk the pending lanes in request order, taking
// every lane whose key AND slot are not yet used this round; duplicates
// stay pending for a later round (skip-and-defer). The k-th request for
// a key still observes the (k-1)-th's committed state — per-key order
// is preserved because the earlier occurrence is always taken first —
// while hot-key batches need only max-multiplicity rounds instead of
// one round per duplicate. Returns lane count m; fills lane_idx
// (original positions), slots, exists.
int64_t gt_batch_next_round(void* bv, int32_t* lane_idx, int32_t* slots,
                            uint8_t* exists) {
  Batch* b = (Batch*)bv;
  Table* t = b->table;
  GT_LOCK(t);
  if (b->pending.empty()) return 0;
  std::unordered_map<std::string_view, int> seen_keys;
  std::unordered_map<int32_t, int> used_slots;
  seen_keys.reserve(b->pending.size() * 2);
  used_slots.reserve(b->pending.size() * 2);
  b->round_lane.clear();
  std::vector<int32_t> deferred;
  int64_t m = 0;
  for (int32_t i : b->pending) {
    std::string_view k = b->key_view(i);
    if (seen_keys.count(k)) {  // duplicate: must see this round's commit
      deferred.push_back(i);
      continue;
    }
    if (!b->resolved[i]) {
      b->resolve(i);
      b->resolved[i] = 1;
    }
    if (used_slots.count(b->slot[i])) {  // eviction collision: defer as-is
      deferred.push_back(i);
      seen_keys.emplace(k, 1);  // later same-key lanes defer too
      continue;
    }
    lane_idx[m] = i;
    slots[m] = b->slot[i];
    exists[m] = b->exists[i];
    b->round_lane.push_back(i);
    seen_keys.emplace(k, 1);
    used_slots.emplace(b->slot[i], 1);
    ++m;
  }
  b->pending.swap(deferred);
  return m;
}

// Commit kernel outputs for the lanes of the LAST emitted round.
void gt_batch_commit_round(void* bv, const int64_t* new_expire,
                           const uint8_t* removed) {
  Batch* b = (Batch*)bv;
  Table* t = b->table;
  GT_LOCK(t);
  for (size_t j = 0; j < b->round_lane.size(); ++j) {
    int32_t i = b->round_lane[j];
    int32_t s = b->slot[i];
    if (s < 0) continue;
    // Staleness guard (slot_table.py::commit keys check): only commit
    // if the slot still maps this lane's key.
    if (!b->owns(i, s)) continue;
    if (removed[j]) t->unmap_slot(s);
    else t->recs[s].expire_ms = new_expire[j];
  }
}

// Plan EVERY round upfront — no interleaved device commits — so the
// whole batch runs as ONE device dispatch (ops/buckets.py apply_rounds:
// a lax.while_loop over rounds).  Per lane i fills round_id / slot /
// exists and returns the round count.
//
// Chained lanes (key already emitted in an earlier round of this batch)
// get exists=1: the device row was just written by this very batch, so
// device-side liveness (expire_at >= now) is authoritative — including
// the remove-then-recreate chain, where the earlier round stamped
// expire_at=0.  This removes the need for host expire updates between
// rounds, which is exactly what forces a blocking device->host readback
// per round in the interleaved design.
// Shared round scheduler for both full-plan entry points: walks
// b->pending (in request order) emitting rounds from `round` upward,
// deferring later same-key occurrences and eviction collisions.
// `occ`/`write` may be null (gt_batch_plan); when present each emitted
// lane gets occ=0, write=1 — every round-scheme lane scatters.
//
// key -> slot at first emission: a later lane is chained (device-
// authoritative) only while it still resolves to that same slot; a
// mid-batch eviction reassigning the key to a fresh slot falls back
// to the host's exists (the state was lost, as in the reference's
// LRU eviction of a live item).
static int64_t plan_rounds(Batch* b, int64_t round, int32_t* round_id,
                           int32_t* slots, uint8_t* exists, int32_t* occ,
                           uint8_t* write,
                           std::unordered_map<int32_t, std::string_view>& slot_owner) {
  Table* t = b->table;
  while (!b->pending.empty()) {
    std::unordered_map<std::string_view, int> seen_keys;
    std::unordered_map<int32_t, int> used_slots;
    seen_keys.reserve(b->pending.size() * 2);
    used_slots.reserve(b->pending.size() * 2);
    std::vector<int32_t> deferred;
    for (int32_t i : b->pending) {
      std::string_view k = b->key_view(i);
      if (seen_keys.count(k)) {
        deferred.push_back(i);
        continue;
      }
      if (!b->resolved[i]) {
        b->resolve(i);
        b->resolved[i] = 1;
      }
      // Slot takeover: a DIFFERENT key's create (mid-batch eviction)
      // is already scheduled on this lane's captured slot — running
      // here would corrupt the new owner's device state.  Re-resolve:
      // this key is no longer mapped, so it gets a fresh slot.
      auto so = slot_owner.find(b->slot[i]);
      if (so != slot_owner.end() && so->second != k) b->resolve(i);
      if (used_slots.count(b->slot[i])) {  // eviction collision: defer as-is
        deferred.push_back(i);
        seen_keys.emplace(k, 1);
        continue;
      }
      round_id[i] = (int32_t)round;
      slots[i] = b->slot[i];
      if (occ != nullptr) occ[i] = 0;
      if (write != nullptr) write[i] = 1;
      so = slot_owner.find(b->slot[i]);
      exists[i] = (so != slot_owner.end() && so->second == k)
                      ? 1  // chained: device state authoritative
                      : b->exists[i];
      b->plan_order.push_back(i);
      ++t->recs[b->slot[i]].pending_write;
      seen_keys.emplace(k, 1);
      slot_owner[b->slot[i]] = k;
      used_slots.emplace(b->slot[i], 1);
    }
    b->pending.swap(deferred);
    ++round;
  }
  return round;
}

int64_t gt_batch_plan(void* bv, int32_t* round_id, int32_t* slots,
                      uint8_t* exists) {
  Batch* b = (Batch*)bv;
  GT_LOCK(b->table);
  b->plan_order.clear();
  b->plan_order.reserve((size_t)b->n);
  std::unordered_map<int32_t, std::string_view> slot_owner;
  slot_owner.reserve((size_t)b->n * 2);
  return plan_rounds(b, 0, round_id, slots, exists, nullptr, nullptr,
                     slot_owner);
}

// Fold the planned batch's kernel outputs (indexed by ORIGINAL lane)
// back into the table, in emission order so the last write per key
// wins.  Unlike the per-round staleness guard, an unmapped slot is
// re-mapped to the lane's key: that is the remove-then-recreate chain
// (token RESET_REMAINING freed it, a later round recreated it on
// device).  A slot owned by a DIFFERENT key means a later in-batch
// eviction took it over — this lane's write is stale, skip.
void gt_batch_commit_plan(void* bv, const int64_t* new_expire,
                          const uint8_t* removed) {
  Batch* b = (Batch*)bv;
  Table* t = b->table;
  GT_LOCK(t);
  b->committed = true;
  const size_t m = b->plan_order.size();
  constexpr size_t kAhead = 8;  // records of the lanes ahead, on their way
  for (size_t j = 0; j < m; ++j) {
    if (j + kAhead < m) {
      int32_t sa = b->slot[b->plan_order[j + kAhead]];
      if (sa >= 0) __builtin_prefetch(&t->recs[sa]);
    }
    int32_t i = b->plan_order[j];
    int32_t s = b->slot[i];
    if (s < 0) continue;
    SlotRec& r = t->recs[s];
    if (r.pending_write > 0) --r.pending_write;
    bool mine = b->owns(i, s);
    if (removed[i]) {
      if (mine) t->unmap_slot(s);
      continue;
    }
    if (mine) {
      // Negative expire is the narrow-wire "unchanged" sentinel
      // (ops/buckets.py unpack_output32): the kernel passed the slot's
      // pre-batch expiry through, so the host value is already right.
      if (new_expire[i] >= 0) r.expire_ms = new_expire[i];
    } else if (!r.mapped) {
      t->remap(s, b->key_ptr(i), b->key_len(i), b->hashes[i], new_expire[i]);
    }
  }
}

// Grouped full plan: uniform duplicate groups collapse into round 0.
//
// A "uniform group" is every lane of one key whose request config
// (algorithm, behavior, hits, limit, duration, greg columns) is
// identical and carries no RESET_REMAINING (whose remove-recreate chain
// is inherently sequential).  Such a group needs no rounds at all: the
// kernel computes each occurrence's response in closed form from the
// occurrence index (ops/buckets.py analytic-duplicate math) and only
// the LAST occurrence scatters.  Lanes that do not qualify fall back to
// the round scheme starting at round 1.  This turns hot-key skew — the
// reference's thundering-herd case (its BATCHING exists for exactly
// this, architecture.md:19-25) — from O(max multiplicity) sequential
// kernel rounds into O(1).
//
// Outputs per lane: round_id, slot, exists, occ (occurrence index
// within a uniform group; 0 otherwise), write (1 when this lane's lane
// scatters state: the last occurrence of a uniform group, or every
// round-scheme lane).  Returns the round count.
//
// The frame's distinct keys are resolved in ONE prefetched pass, at
// every width (a batch of 4 runs the same loop as one of 4096): while
// group g takes its LRU touch and liveness test, group g+3D's index
// line, group g+2D's slot record (the hash-bit probe names it) and
// group g+D's LRU neighbours are on their way from memory.  What the
// probe saw may be stale by the time its group's turn comes (an earlier
// group's miss evicted the key): the record's own key bytes decide,
// and a refusal re-walks the chain.  A key the probe found ABSENT stays
// absent until its own turn — within a plan only a key's own group
// inserts it — so it goes straight to Table::assign, in request order.
int64_t gt_batch_plan_grouped(void* bv, const int32_t* algo,
                              const int32_t* behavior, const int64_t* hits,
                              const int64_t* limit, const int64_t* duration,
                              const int64_t* greg_e, const int64_t* greg_d,
                              int32_t reset_mask, int32_t* round_id,
                              int32_t* slots, uint8_t* exists, int32_t* occ,
                              uint8_t* write) {
  Batch* b = (Batch*)bv;
  Table* t = b->table;
  GT_LOCK(t);
  const int64_t n = b->n;
  b->plan_order.clear();
  b->plan_order.reserve((size_t)n);

  // Group lanes by key, preserving first-appearance order, in a flat
  // table over the hashes the batch carries (a hash match is confirmed
  // against the first member's key bytes in the borrowed buffer), and
  // lay the members out as a CSR (gid pass -> counting sort): no node
  // and no vector a group.  Every array is the table's scratch.
  Table::PlanScratch& sc = t->scratch;
  size_t gsz = 8;
  int gshift = 61;  // 64 - log2(gsz)
  while (gsz < (size_t)n * 2) { gsz <<= 1; --gshift; }
  const size_t gmask = gsz - 1;
  sc.gtab.assign(gsz, -1);
  sc.gid.resize((size_t)n);
  sc.gfirst.clear();
  sc.gcount.clear();
  for (int64_t i = 0; i < n; ++i) {
    const uint64_t h = b->hashes[i];
    size_t p = (size_t)((h * 0x9e3779b97f4a7c15ull) >> gshift);
    int32_t g;
    while ((g = sc.gtab[p]) >= 0) {
      int32_t f = sc.gfirst[(size_t)g];
      if (b->hashes[f] == h && b->key_view(f) == b->key_view(i)) break;
      p = (p + 1) & gmask;
    }
    if (g < 0) {
      g = (int32_t)sc.gfirst.size();
      sc.gtab[p] = g;
      sc.gfirst.push_back((int32_t)i);
      sc.gcount.push_back(0);
    }
    sc.gid[(size_t)i] = g;
    ++sc.gcount[(size_t)g];
  }
  const int64_t n_groups = (int64_t)sc.gfirst.size();
  // CSR offsets + member fill (members of one group stay in request
  // order — the occurrence index below depends on it).
  sc.goff.resize((size_t)n_groups + 1);
  sc.goff[0] = 0;
  for (int64_t g = 0; g < n_groups; ++g) sc.goff[(size_t)g + 1] = sc.goff[(size_t)g] + sc.gcount[(size_t)g];
  sc.gmembers.resize((size_t)n);
  sc.cursor.assign(sc.goff.begin(), sc.goff.end() - 1);
  for (int64_t i = 0; i < n; ++i)
    sc.gmembers[(size_t)sc.cursor[(size_t)sc.gid[(size_t)i]]++] = (int32_t)i;

  sc.mh.resize((size_t)n_groups);
  sc.cand.resize((size_t)n_groups);
  // Round-0 groups' first lanes: all a slow lane needs to learn who
  // owns a round-0 slot, and nothing reads it before one exists.
  sc.r0.clear();
  sc.slow.clear();  // lanes for the round scheme
  constexpr int64_t D = 16;  // groups between the stages of the pass
  for (int64_t g = -3 * D; g < n_groups; ++g) {
    if (int64_t a = g + 3 * D; a < n_groups) {
      sc.mh[(size_t)a] = t->index.mix(b->hashes[sc.gfirst[(size_t)a]]);
      t->index.prefetch(sc.mh[(size_t)a]);
    }
    if (int64_t p = g + 2 * D; p >= 0 && p < n_groups) {
      int32_t c = sc.cand[(size_t)p] = t->index.probe_bits(sc.mh[(size_t)p]);
      if (c >= 0) __builtin_prefetch(&t->recs[c]);
    }
    if (int64_t q = g + D; q >= 0 && q < n_groups && sc.cand[(size_t)q] >= 0) {
      const SlotRec& r = t->recs[sc.cand[(size_t)q]];
      if (r.lru_prev >= 0) __builtin_prefetch(&t->recs[r.lru_prev]);
      if (r.lru_next >= 0) __builtin_prefetch(&t->recs[r.lru_next]);
    }
    if (g < 0) continue;

    const int32_t* mem = sc.gmembers.data() + sc.goff[(size_t)g];
    size_t g_size = (size_t)(sc.goff[(size_t)g + 1] - sc.goff[(size_t)g]);
    int32_t first = mem[0];
    bool uniform = (behavior[first] & reset_mask) == 0;
    for (size_t j = 1; uniform && j < g_size; ++j) {
      int32_t i = mem[j];
      uniform = algo[i] == algo[first] && behavior[i] == behavior[first] &&
                hits[i] == hits[first] && limit[i] == limit[first] &&
                duration[i] == duration[first] &&
                greg_e[i] == greg_e[first] && greg_d[i] == greg_d[first];
    }
    const char* key = b->key_ptr(first);
    const size_t len = b->key_len(first);
    const uint64_t h = b->hashes[first];
    int64_t ev_before = t->front_evictions;
    int32_t c = sc.cand[(size_t)g];
    if (c >= 0 && !t->recs[c].key_is(key, len))
      c = t->index.walk(sc.mh[(size_t)g],
                        [&](int32_t o) { return t->recs[o].key_is(key, len); });
    auto [s, e] = c >= 0 ? t->hit(c, b->now_ms) : t->assign(key, len, h, b->now_ms);
    b->slot[first] = s;
    b->exists[first] = e ? 1 : 0;
    b->resolved[first] = 1;
    // An eviction may have stolen the slot from a key with EARLIER
    // lanes in this batch; scheduling this group in round 0 would run
    // the create before the victim's lanes.  Demote to the slow path,
    // whose per-round slot-collision deferral orders it correctly.
    // (Without an eviction s cannot already carry a round-0 group: a
    // hit's slot maps this key and no other, and a free slot maps none.)
    bool evicted = t->front_evictions != ev_before;
    if (uniform && !evicted) {
      sc.r0.push_back(first);
      ++t->recs[s].pending_write;
      for (size_t j = 0; j < g_size; ++j) {
        int32_t i = mem[j];
        round_id[i] = 0;
        slots[i] = s;
        exists[i] = e ? 1 : 0;
        occ[i] = (int32_t)j;
        write[i] = (j + 1 == g_size) ? 1 : 0;
        b->slot[i] = s;
        if (write[i]) b->plan_order.push_back(i);
      }
    } else {
      for (size_t j = 0; j < g_size; ++j) sc.slow.push_back(mem[j]);
    }
  }
  if (sc.slow.empty()) return 1;

  // Round scheme for the leftovers, starting at round 1 (round 0 is the
  // grouped dispatch).  Same chaining/deferral rules as gt_batch_plan.
  // Seed the slot-owner map with round-0 groups so slow lanes detect
  // takeovers of (and chain onto) grouped slots.
  std::unordered_map<int32_t, std::string_view> slot_owner;
  slot_owner.reserve((size_t)n * 2);
  for (int32_t f : sc.r0) slot_owner[b->slot[f]] = b->key_view(f);
  std::sort(sc.slow.begin(), sc.slow.end());
  b->pending.assign(sc.slow.begin(), sc.slow.end());
  return plan_rounds(b, 1, round_id, slots, exists, occ, write, slot_owner);
}

void gt_batch_free(void* bv) {
  Batch* b = (Batch*)bv;
  // A planned-but-never-committed batch (error path) must release its
  // pending-write claims or the slots stay device-authoritative forever.
  // Locked: Python GC can run this from any thread while a younger
  // batch's plan is mid-flight on the same table.
  if (!b->committed) {
    Table* t = b->table;
    GT_LOCK(t);
    for (int32_t i : b->plan_order) {
      int32_t s = b->slot[i];
      if (s >= 0 && t->recs[s].pending_write > 0) --t->recs[s].pending_write;
    }
  }
  delete b;
}

// ---------------------------------------------------------------------
// FNV-1 / FNV-1a 64 over a packed key batch (replicated_hash.go:31 uses
// fasthash/fnv1; host-side ring lookups hash every key of every batch).
void gt_fnv1_batch(const char* keys, const int64_t* offsets, int64_t n,
                   int32_t variant_1a, uint64_t* out) {
  for (int64_t i = 0; i < n; ++i) {
    const char* p = keys + offsets[i];
    const char* end = keys + offsets[i + 1];
    out[i] = variant_1a ? fnv1a64(p, end) : fnv1_64(p, end);
  }
}

}  // extern "C"

namespace {
// ---------------------------------------------------------------------
// Mesh planner: shard-bucket + per-shard grouped round planning + padded
// fill + decode/commit for a WHOLE device mesh in single C++ calls.
//
// parallel/mesh.py round 3 ran this as a serial Python loop over shards
// (hash -> argsort -> per-shard subset/make_columns -> NativeBatchPlanner
// -> padded array fill, then per-shard decode + commit) — ~2.7ms of the
// ~5.4ms host cost per 1000-lane service batch.  The reference serves
// its whole edge in compiled code (gubernator.go:116-227); this closes
// the same gap for the columnar ingress.  Call sequence per batch (all
// under the store lock, ColumnarPipeline discipline):
//
//   gt_mesh_begin(tables[S], keys, n)    -> handle + per-shard counts
//   gt_mesh_plan_grouped(h, cols, P, ..) -> padded [S,P] plan arrays,
//                                           pos[n] (lane -> padded idx)
//   gt_mesh_encode_wire(plan arrays, cols, ..) -> the ONE i32 buffer the
//                                           stage uploads (either wire)
//   ... device dispatch ...
//   gt_mesh_finish_{narrow,wide}(h, ..)  -> response columns in ORIGINAL
//                                           order + slot-table commit
//   gt_mesh_free(h)

struct MeshPlan {
  int64_t S = 0, n = 0, now_ms = 0, P = 0;
  std::vector<Table*> tables;
  std::vector<std::vector<char>> skeys;      // per-shard packed key bytes
  std::vector<std::vector<int64_t>> soffs;   // per-shard offsets [m+1]
  std::vector<std::vector<uint64_t>> shash;  // per-shard FNV-1a 64 [m]
  std::vector<std::vector<int32_t>> lanes;   // per-shard original lane ids
  std::vector<void*> batches;                // per-shard Batch* (plan phase)
  std::vector<std::vector<int32_t>> pslot;   // per-shard planned slots [m]
  std::vector<std::vector<int64_t>> pre_exp; // plan-time expiry snapshot [m]
};

// The wire's encode (gt_mesh_encode_wire).  The layouts are those of
// ops/buckets.py, which keeps the numpy packers as the reference the
// tests hold this to: pack_dict_wire / pack_lane_wire / set_wire_header
// on the host, unpack_dict_wire / unpack_lane_wire inside the program.
constexpr int64_t kDictTableRows = 256;  // DICT_TABLE_ROWS
constexpr int64_t kDictTableWords = 2 * kDictTableRows + 5 * 2 * kDictTableRows;
constexpr int64_t kWireHeaderWords = 4;  // WIRE_HEADER_WORDS
constexpr int64_t kLaneWords = 11;       // LANE_WIRE_WORDS
constexpr int64_t kLaneWordsWide = 16;   // LANE_WIRE_WORDS_WIDE

// One configuration: what the dictionary wire's table holds a row of.
// v[0], v[1] are algorithm and behaviour; v[5] is greg_expire as the
// delta from now (0 where greg_duration, v[6], is 0).
struct WireConfig {
  int64_t v[7];
  bool operator==(const WireConfig& o) const {
    uint64_t d = 0;
    for (int k = 0; k < 7; ++k) d |= (uint64_t)(v[k] ^ o.v[k]);
    return d == 0;
  }
};

// Seven independent multiplies (odd constants), then one mix: the
// table below is probed by the low bits.
inline uint64_t wire_config_hash(const WireConfig& c) {
  static const uint64_t K[7] = {
      0x9E3779B97F4A7C15ull, 0xC2B2AE3D27D4EB4Full, 0x165667B19E3779F9ull,
      0xFF51AFD7ED558CCDull, 0xC4CEB9FE1A85EC53ull, 0xD6E8FEB86659FD93ull,
      0x27D4EB2F165667C5ull};
  uint64_t h = 0;
  for (int k = 0; k < 7; ++k) h += (uint64_t)c.v[k] * K[k];
  h ^= h >> 29;
  h *= 0xBF58476D1CE4E5B9ull;
  return h ^ (h >> 32);
}

inline int32_t lo32(int64_t v) { return (int32_t)(uint32_t)(uint64_t)v; }
inline int32_t hi32(int64_t v) { return (int32_t)(v >> 32); }
// a - b as numpy's i64 takes it: it wraps.
inline int64_t sub64(int64_t a, int64_t b) {
  return (int64_t)((uint64_t)a - (uint64_t)b);
}

}  // namespace

extern "C" {

// Phase 1: hash every key (fnv1a-64 % S, the static shardmap of
// parallel/mesh.py shard_of_key) and bucket keys/lanes per shard; the
// hash goes with the key, so the shard's table is probed by it and no
// key is hashed twice.  Fills counts[S]; returns the handle.
void* gt_mesh_begin(void** tables, int64_t S, const char* keys,
                    const int64_t* offsets, int64_t n, int64_t now_ms,
                    int64_t* counts) {
  MeshPlan* mp = new MeshPlan();
  mp->S = S;
  mp->n = n;
  mp->now_ms = now_ms;
  mp->tables.assign((Table**)tables, (Table**)tables + S);
  mp->skeys.resize(S);
  mp->soffs.resize(S);
  mp->shash.resize(S);
  mp->lanes.resize(S);
  mp->batches.assign(S, nullptr);
  mp->pslot.resize(S);
  mp->pre_exp.resize(S);

  std::vector<int32_t> shard_of((size_t)n);
  std::vector<uint64_t> hash_of((size_t)n);
  std::vector<int64_t> bytes_of((size_t)S, 0);
  for (int64_t i = 0; i < n; ++i) {
    uint64_t h = fnv1a64(keys + offsets[i], keys + offsets[i + 1]);
    int32_t s = (int32_t)(h % (uint64_t)S);
    hash_of[i] = h;
    shard_of[i] = s;
    counts[s]++;
    bytes_of[s] += offsets[i + 1] - offsets[i];
  }
  for (int64_t s = 0; s < S; ++s) {
    mp->skeys[s].reserve((size_t)bytes_of[s]);
    mp->soffs[s].reserve((size_t)counts[s] + 1);
    mp->soffs[s].push_back(0);
    mp->shash[s].reserve((size_t)counts[s]);
    mp->lanes[s].reserve((size_t)counts[s]);
  }
  for (int64_t i = 0; i < n; ++i) {
    int32_t s = shard_of[i];
    mp->skeys[s].insert(mp->skeys[s].end(), keys + offsets[i],
                        keys + offsets[i + 1]);
    mp->soffs[s].push_back((int64_t)mp->skeys[s].size());
    mp->shash[s].push_back(hash_of[i]);
    mp->lanes[s].push_back((int32_t)i);
  }
  return mp;
}

// Phase 2: per-shard grouped planning straight into padded [S, P]
// row-major outputs (callers pre-fill slot with -1 and the rest with 0;
// this writes only lanes [0, m_s) of each row).  Column inputs are
// FULL-batch arrays indexed by original lane.  pos[i] = s*P + j maps
// each original lane to its padded position, so numpy fills value/cfg
// columns with one vectorized scatter per column.  Returns n_rounds
// (max over shards).
int64_t gt_mesh_plan_grouped(void* mpv, const int32_t* algo,
                             const int32_t* behavior, const int64_t* hits,
                             const int64_t* limit, const int64_t* duration,
                             const int64_t* greg_e, const int64_t* greg_d,
                             int32_t reset_mask, int64_t P, int32_t* slot,
                             int32_t* rid, uint8_t* exists, int32_t* occ,
                             uint8_t* write, int64_t* pos) {
  MeshPlan* mp = (MeshPlan*)mpv;
  mp->P = P;
  int64_t n_rounds = 1;
  std::vector<int32_t> a32, b32, rid_t, slot_t, occ_t;
  std::vector<int64_t> h64, l64, d64, ge64, gd64;
  std::vector<uint8_t> ex_t, wr_t;
  for (int64_t s = 0; s < mp->S; ++s) {
    int64_t m = (int64_t)mp->lanes[s].size();
    if (m == 0) continue;
    // One shard's whole plan (batch begin + grouped plan + pre_exp
    // snapshot) runs under that shard's table lock: atomic against a
    // concurrent older batch's finish on the same shard (the
    // overlapped-pipeline contract; the gt_batch_* calls below
    // re-enter the same recursive mutex).
    GT_LOCK(mp->tables[s]);
    // Gather this shard's column values into contiguous temporaries.
    a32.resize(m); b32.resize(m);
    h64.resize(m); l64.resize(m); d64.resize(m);
    ge64.resize(m); gd64.resize(m);
    for (int64_t j = 0; j < m; ++j) {
      int32_t i = mp->lanes[s][j];
      a32[j] = algo[i]; b32[j] = behavior[i];
      h64[j] = hits[i]; l64[j] = limit[i]; d64[j] = duration[i];
      ge64[j] = greg_e[i]; gd64[j] = greg_d[i];
    }
    rid_t.assign(m, 0); slot_t.resize(m); occ_t.assign(m, 0);
    ex_t.resize(m); wr_t.resize(m);
    void* b = new Batch(mp->tables[s], mp->skeys[s].data(),
                        mp->soffs[s].data(), m, mp->now_ms,
                        mp->shash[s].data());
    mp->batches[s] = b;
    int64_t nr = gt_batch_plan_grouped(
        b, a32.data(), b32.data(), h64.data(), l64.data(), d64.data(),
        ge64.data(), gd64.data(), reset_mask, rid_t.data(), slot_t.data(),
        ex_t.data(), occ_t.data(), wr_t.data());
    if (nr > n_rounds) n_rounds = nr;
    Table* t = mp->tables[s];
    int64_t base = s * P;
    mp->pslot[s].assign(slot_t.begin(), slot_t.end());
    mp->pre_exp[s].resize(m);
    for (int64_t j = 0; j < m; ++j) {
      slot[base + j] = slot_t[j];
      rid[base + j] = rid_t[j];
      exists[base + j] = ex_t[j];
      occ[base + j] = occ_t[j];
      write[base + j] = wr_t[j];
      pos[mp->lanes[s][j]] = base + j;
      // Plan-time expiry snapshot for the narrow keep-sentinel decode
      // (models/shard.py decode_narrow passthrough semantics).
      int32_t sl = slot_t[j];
      mp->pre_exp[s][j] =
          (sl >= 0 && sl < t->capacity) ? t->recs[sl].expire_ms : 0;
    }
  }
  return n_rounds;
}

// Phase 2b: the wire.  One pass over the n request lanes interns each
// lane's configuration in an open-addressed table that compares the
// seven values themselves (exact: two configurations never share a
// row, whatever they hash to) and counts ALL the frame's distinct
// ones; then the rule picks the wire and the buffer is filled, header
// included, every word of it written once, in order.  The DICTIONARY
// wire where the frame has at most 256 configurations, at most 255
// rounds, no `occ` past 65,535 and the caller does not force the
// per-lane one: rows of 3P + kDictTableWords + 4 words (slot; occ |
// flags << 16 | cfg << 24; round id; the table, rows in order of first
// appearance, a copy a shard row).  Else the PER-LANE wire: rows of
// 11P + 4 words, 16P + 4 where the answer is wide (`narrow` 0):
// greg_expire as the delta from now on the narrow one, absolute on the
// wide one.  Where the rule is decided before any count (forced, or
// past 255 rounds) nothing is interned and *config_rows is 0; an empty
// frame takes the per-lane wire.  `wire` holds S * max(dict_row,
// lane_row) words; the S rows are written back to back at the chosen
// width.  The caller passes the two widths it allocated by, and a
// width this file would not write is refused (-1) before a word is
// written.  Returns 1 for the per-lane wire, 0 for the dictionary's.
int32_t gt_mesh_encode_wire(
    int64_t S, int64_t P, int64_t n, const int32_t* slot,
    const uint8_t* exists, const uint8_t* write, const int32_t* occ,
    const int32_t* rid, const int64_t* pos, const int32_t* algo,
    const int32_t* behavior, const int64_t* hits, const int64_t* limit,
    const int64_t* duration, const int64_t* greg_e, const int64_t* greg_d,
    int64_t now_ms, int64_t n_rounds, int32_t narrow, int32_t force_lanes,
    int64_t dict_row, int64_t lane_row, int32_t* wire,
    int64_t* config_rows) {
  const int64_t lane_words = narrow ? kLaneWords : kLaneWordsWide;
  if (dict_row != 3 * P + kDictTableWords + kWireHeaderWords ||
      lane_row != lane_words * P + kWireHeaderWords)
    return -1;

  std::vector<WireConfig> configs;
  std::vector<uint8_t> cfg_at;  // [S, P]: a lane's row of the table
  bool dict = !force_lanes && n_rounds <= 255 && n > 0;
  if (dict) {
    size_t cap = 16;
    while (cap < (size_t)n * 2) cap <<= 1;
    std::vector<int32_t> index(cap, -1);
    cfg_at.assign((size_t)(S * P), 0);
    configs.reserve((size_t)kDictTableRows + 1);
    for (int64_t i = 0; i < n; ++i) {
      WireConfig c{{algo[i], behavior[i], hits[i], limit[i], duration[i],
                    greg_d[i] != 0 ? sub64(greg_e[i], now_ms) : 0, greg_d[i]}};
      size_t at = (size_t)wire_config_hash(c) & (cap - 1);
      while (index[at] >= 0 && !(configs[(size_t)index[at]] == c))
        at = (at + 1) & (cap - 1);
      if (index[at] < 0) {
        index[at] = (int32_t)configs.size();
        // A frame past the table (a limit a key) may hold n: one move
        // of the 257, not a doubling's copies all the way up.
        if ((int64_t)configs.size() == kDictTableRows + 1)
          configs.reserve((size_t)n);
        configs.push_back(c);
      }
      // Past 255 the frame has left the dictionary: the count goes on,
      // the rows are not used.
      cfg_at[(size_t)pos[i]] = (uint8_t)index[at];
    }
    dict = (int64_t)configs.size() <= kDictTableRows;
    if (dict) {
      int32_t top = 0;
      for (int64_t k = 0; k < S * P; ++k) top = std::max(top, occ[k]);
      dict = top <= 65535;
    }
  }
  *config_rows = (int64_t)configs.size();

  const int64_t W = dict ? dict_row : lane_row;
  auto header = [&](int32_t* row) {
    int32_t* h = row + W - kWireHeaderWords;
    h[0] = (int32_t)n_rounds;
    h[1] = lo32(now_ms);
    h[2] = hi32(now_ms);
    h[3] = 0;
  };

  if (dict) {
    // Shard 0's table, then a copy a row.
    int32_t* table = wire + 3 * P;
    std::memset(table, 0, sizeof(int32_t) * kDictTableWords);
    for (size_t k = 0; k < configs.size(); ++k) {
      const int64_t* v = configs[k].v;
      table[k] = (int32_t)v[0];
      table[kDictTableRows + k] = (int32_t)v[1];
      for (int f = 2; f < 7; ++f) {
        int32_t* pair = table + (2 + 2 * (f - 2)) * kDictTableRows;
        pair[k] = lo32(v[f]);
        pair[kDictTableRows + k] = hi32(v[f]);
      }
    }
    for (int64_t s = 0; s < S; ++s) {
      int32_t* row = wire + s * W;
      const int64_t base = s * P;
      std::memcpy(row, slot + base, sizeof(int32_t) * P);
      int32_t* meta = row + P;
      for (int64_t j = 0; j < P; ++j)
        meta[j] = (int32_t)((uint32_t)(occ[base + j] & 0xFFFF) |
                            (uint32_t)(exists[base + j] | (write[base + j] << 1)) << 16 |
                            (uint32_t)cfg_at[(size_t)(base + j)] << 24);
      std::memcpy(row + 2 * P, rid + base, sizeof(int32_t) * P);
      if (s) std::memcpy(row + 3 * P, table, sizeof(int32_t) * kDictTableWords);
      header(row);
    }
    return 0;
  }

  // The request at each place of the plan (-1: padding, which reads
  // zero in every column but slot's), so the rows are written through
  // in order and no place is divided back into shard and lane.
  std::vector<int32_t> req_at((size_t)(S * P), -1);
  for (int64_t i = 0; i < n; ++i) req_at[(size_t)pos[i]] = (int32_t)i;
  for (int64_t s = 0; s < S; ++s) {
    int32_t* row = wire + s * W;
    const int64_t base = s * P;
    const int32_t* at = req_at.data() + base;
    std::memcpy(row, slot + base, sizeof(int32_t) * P);
    int32_t* flags = row + P;
    for (int64_t j = 0; j < P; ++j)
      flags[j] = exists[base + j] | (write[base + j] << 1);
    std::memcpy(row + 4 * P, occ + base, sizeof(int32_t) * P);
    std::memcpy(row + 5 * P, rid + base, sizeof(int32_t) * P);
    auto column = [&](int64_t k, auto value) {
      int32_t* col = row + k * P;
      for (int64_t j = 0; j < P; ++j) col[j] = at[j] >= 0 ? value(at[j]) : 0;
    };
    column(2, [&](int32_t i) { return algo[i]; });
    column(3, [&](int32_t i) { return behavior[i]; });
    if (narrow) {
      column(6, [&](int32_t i) { return lo32(hits[i]); });
      column(7, [&](int32_t i) { return lo32(limit[i]); });
      column(8, [&](int32_t i) { return lo32(duration[i]); });
      column(9, [&](int32_t i) {
        return greg_d[i] != 0 ? lo32(sub64(greg_e[i], now_ms)) : 0;
      });
      column(10, [&](int32_t i) { return lo32(greg_d[i]); });
    } else {
      const int64_t* values[5] = {hits, limit, duration, greg_e, greg_d};
      for (int f = 0; f < 5; ++f) {
        const int64_t* v = values[f];
        column(6 + 2 * f, [&](int32_t i) { return lo32(v[i]); });
        column(7 + 2 * f, [&](int32_t i) { return hi32(v[i]); });
      }
    }
    header(row);
  }
  return 1;
}

// Phase 3 (narrow wire): decode the packed i32[S, 4, P] device result,
// commit each shard's plan into its slot table, and scatter responses
// into ORIGINAL-order output columns.  Sentinels (ops/buckets.py
// apply_rounds32): row2/row3 are deltas from now; -1 = absolute 0,
// -2 = unchanged pass-through (reconstructed from the live table when
// the slot still maps this lane's key, else the plan-time snapshot).
void gt_mesh_finish_narrow(void* mpv, const int32_t* packed, int64_t now_ms,
                           int32_t* status, int64_t* remaining,
                           int64_t* reset_time) {
  MeshPlan* mp = (MeshPlan*)mpv;
  int64_t P = mp->P;
  std::vector<int64_t> ne;
  std::vector<uint8_t> rm;
  for (int64_t s = 0; s < mp->S; ++s) {
    int64_t m = (int64_t)mp->lanes[s].size();
    if (m == 0) continue;
    Table* t = mp->tables[s];
    GT_LOCK(t);
    Batch* b = (Batch*)mp->batches[s];
    const int32_t* row0 = packed + ((s * 4) + 0) * P;
    const int32_t* row1 = packed + ((s * 4) + 1) * P;
    const int32_t* row2 = packed + ((s * 4) + 2) * P;
    const int32_t* row3 = packed + ((s * 4) + 3) * P;
    ne.resize(m);
    rm.resize(m);
    for (int64_t j = 0; j < m; ++j) {
      int32_t orig = mp->lanes[s][j];
      status[orig] = row0[j] & 1;
      rm[j] = (uint8_t)((row0[j] >> 1) & 1);
      remaining[orig] = (int64_t)row1[j];
      int32_t d2 = row2[j];
      if (d2 == -1) {
        reset_time[orig] = 0;
      } else if (d2 == -2) {
        // Keep-sentinel: prefer the live table value while the slot
        // still maps this lane's key (decode_narrow defense in depth).
        int32_t sl = mp->pslot[s][j];
        bool mine = sl >= 0 && sl < t->capacity && b->owns(j, sl);
        reset_time[orig] = mine ? t->recs[sl].expire_ms : mp->pre_exp[s][j];
      } else {
        reset_time[orig] = (int64_t)d2 + now_ms;
      }
      int32_t d3 = row3[j];
      // -1 decodes to absolute 0 (removed/no-reset; commit_plan WRITES
      // expire_ms=0); -2 decodes to -1 so commit_plan skips the
      // already-correct host value (unpack_output32 parity).
      ne[j] = (d3 == -1) ? 0 : (d3 == -2 ? -1 : (int64_t)d3 + now_ms);
    }
    gt_batch_commit_plan(b, ne.data(), rm.data());
  }
}

// Phase 3 (wide wire): same shape over the packed wide result with
// absolute values, as it leaves the device: i32[S, 8, P], the four rows
// of ops/buckets.py _pack_output as their lo planes (rows 0-3) and then
// their hi planes (rows 4-7).  A lane's 64 bits are put together here,
// where every lane is walked anyway; the lo word is unsigned.
void gt_mesh_finish_wide(void* mpv, const int32_t* packed, int32_t* status,
                         int64_t* remaining, int64_t* reset_time) {
  MeshPlan* mp = (MeshPlan*)mpv;
  int64_t P = mp->P;
  std::vector<int64_t> ne;
  std::vector<uint8_t> rm;
  for (int64_t s = 0; s < mp->S; ++s) {
    int64_t m = (int64_t)mp->lanes[s].size();
    if (m == 0) continue;
    GT_LOCK(mp->tables[s]);
    Batch* b = (Batch*)mp->batches[s];
    const int32_t* lo = packed + s * 8 * P;
    const int32_t* hi = lo + 4 * P;
    auto row = [&](int k, int64_t j) {
      return (int64_t)(((uint64_t)(uint32_t)hi[k * P + j] << 32) |
                       (uint32_t)lo[k * P + j]);
    };
    ne.resize(m);
    rm.resize(m);
    for (int64_t j = 0; j < m; ++j) {
      int32_t orig = mp->lanes[s][j];
      status[orig] = lo[j] & 1;
      rm[j] = (uint8_t)((lo[j] >> 1) & 1);
      remaining[orig] = row(1, j);
      reset_time[orig] = row(2, j);
      ne[j] = row(3, j);
    }
    gt_batch_commit_plan(b, ne.data(), rm.data());
  }
}

void gt_mesh_free(void* mpv) {
  MeshPlan* mp = (MeshPlan*)mpv;
  for (void* b : mp->batches)
    if (b) gt_batch_free(b);
  delete mp;
}

}  // extern "C"

namespace {
// ---------------------------------------------------------------------
// JSON edge: GetRateLimits request parser + response renderer.
//
// The gateway's hot path (gateway.py parse_columns/render_columns) is
// per-lane Python; at the reference's 1000-item request cap that costs
// more host time than the whole device dispatch.  This parser handles
// the gateway's actual wire shape — {"requests":[{flat objects}]} with
// proto3-JSON conventions (int64 as string, enums as names or ints) —
// and REFUSES anything fancier (escape sequences inside name/unique
// key, floats, nested values in known fields) by returning NULL so the
// Python path keeps full fidelity.  Outputs are kernel-ready columns
// plus packed hash keys (name + '_' + unique_key), per-lane validation
// codes (empty unique_key/name, bad enums — gubernator.go:142-152
// semantics), and (offset,len) spans of name/unique_key in the body so
// Python can materialize strings lazily for the rare slow lanes.

struct JsonBatch {
  std::vector<int32_t> algo, behavior;
  std::vector<int64_t> hits, limit, duration;
  std::vector<uint8_t> err;  // 0 ok, 1 empty uk, 2 empty name, 3 bad algo, 4 bad behavior
  std::string hk;
  std::vector<int64_t> hkoff;
  std::vector<int64_t> nspan, ukspan;  // 2*n: (off,len) into body
};

struct JsonCursor {
  const char* p;
  const char* end;
  bool ok = true;

  void ws() {
    while (p < end && (*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r')) p++;
  }
  bool lit(char c) {
    ws();
    if (p < end && *p == c) { p++; return true; }
    return false;
  }
  // Raw string token; fails (ok=false) on escapes/EOF.  Returns
  // (offset, len) into the body.
  bool str(int64_t* off, int64_t* len, const char* base) {
    ws();
    if (p >= end || *p != '"') return false;
    p++;
    const char* s = p;
    while (p < end && *p != '"') {
      if (*p == '\\') { ok = false; return false; }
      p++;
    }
    if (p >= end) { ok = false; return false; }
    *off = s - base;
    *len = p - s;
    p++;
    return true;
  }
  // Integer, optionally quoted (proto3 int64-as-string).  Floats and
  // >18-digit magnitudes poison the cursor (Python fallback).
  bool integer(int64_t* out) {
    ws();
    bool quoted = p < end && *p == '"';
    if (quoted) p++;
    bool neg = false;
    if (p < end && (*p == '-' || *p == '+')) { neg = *p == '-'; p++; }
    if (p >= end || *p < '0' || *p > '9') { ok = false; return false; }
    int64_t v = 0;
    int digits = 0;
    while (p < end && *p >= '0' && *p <= '9') {
      v = v * 10 + (*p - '0');
      if (++digits > 18) { ok = false; return false; }
      p++;
    }
    if (p < end && (*p == '.' || *p == 'e' || *p == 'E')) { ok = false; return false; }
    if (quoted) {
      if (p >= end || *p != '"') { ok = false; return false; }
      p++;
    }
    *out = neg ? -v : v;
    return true;
  }
  // Skip any JSON value (for unknown fields); handles escapes fine
  // since it never extracts content.
  bool skip_value() {
    ws();
    if (p >= end) { ok = false; return false; }
    char c = *p;
    if (c == '"') {
      p++;
      while (p < end && *p != '"') {
        if (*p == '\\') p++;
        p++;
      }
      if (p >= end) { ok = false; return false; }
      p++;
      return true;
    }
    if (c == '{' || c == '[') {
      char close = c == '{' ? '}' : ']';
      p++;
      int depth = 1;
      while (p < end && depth > 0) {
        char d = *p;
        if (d == '"') {
          p++;
          while (p < end && *p != '"') {
            if (*p == '\\') p++;
            p++;
          }
          if (p >= end) { ok = false; return false; }
        } else if (d == '{' || d == '[') depth++;
        else if (d == '}' || d == ']') depth--;
        p++;
      }
      (void)close;
      if (depth != 0) { ok = false; return false; }
      return true;
    }
    // number / true / false / null
    while (p < end && *p != ',' && *p != '}' && *p != ']' && *p != ' ' &&
           *p != '\t' && *p != '\n' && *p != '\r')
      p++;
    return true;
  }
};

bool key_is(const char* base, int64_t off, int64_t len, const char* name) {
  return (int64_t)strlen(name) == len && memcmp(base + off, name, len) == 0;
}

bool token_is(const char* base, int64_t off, int64_t len, const char* name) {
  return key_is(base, off, len, name);
}

}  // namespace

extern "C" {

void* gt_json_parse(const char* body, int64_t blen) {
  JsonCursor c{body, body + blen};
  auto* jb = new JsonBatch();
  auto fail = [&]() -> void* { delete jb; return nullptr; };

  if (!c.lit('{')) return fail();
  bool found_requests = false;
  if (c.lit('}')) {  // {} — still reject trailing garbage (json.loads parity)
    c.ws();
    if (c.p != c.end) return fail();
    jb->hkoff.push_back(0);
    return jb;
  }
  while (true) {
    int64_t koff, klen;
    if (!c.str(&koff, &klen, body)) return fail();
    if (!c.lit(':')) return fail();
    if (key_is(body, koff, klen, "requests")) {
      // Duplicate "requests" keys: json.loads is last-wins; appending
      // would double the batch.  Rare and weird — Python fallback.
      if (found_requests) return fail();
      found_requests = true;
      if (!c.lit('[')) return fail();
      if (!c.lit(']')) {
        while (true) {
          if (!c.lit('{')) return fail();
          int32_t algo = 0, behavior = 0;
          int64_t hits = 0, limit = 0, duration = 0;
          int64_t noff = 0, nlen = 0, uoff = 0, ulen = 0;
          uint8_t err = 0;
          if (!c.lit('}')) {
            while (true) {
              int64_t foff, flen;
              if (!c.str(&foff, &flen, body)) return fail();
              if (!c.lit(':')) return fail();
              if (key_is(body, foff, flen, "name")) {
                if (!c.str(&noff, &nlen, body)) return fail();
              } else if (key_is(body, foff, flen, "uniqueKey") ||
                         key_is(body, foff, flen, "unique_key")) {
                if (!c.str(&uoff, &ulen, body)) return fail();
              } else if (key_is(body, foff, flen, "hits")) {
                if (!c.integer(&hits)) return fail();
              } else if (key_is(body, foff, flen, "limit")) {
                if (!c.integer(&limit)) return fail();
              } else if (key_is(body, foff, flen, "duration")) {
                if (!c.integer(&duration)) return fail();
              } else if (key_is(body, foff, flen, "algorithm")) {
                c.ws();
                if (c.p < c.end && *c.p == '"') {
                  int64_t aoff, alen;
                  if (!c.str(&aoff, &alen, body)) return fail();
                  if (token_is(body, aoff, alen, "TOKEN_BUCKET")) algo = 0;
                  else if (token_is(body, aoff, alen, "LEAKY_BUCKET")) algo = 1;
                  else {
                    // quoted int (proto3 tolerance) or invalid
                    JsonCursor t{body + aoff, body + aoff + alen};
                    int64_t v;
                    if (t.integer(&v) && t.p == t.end && v >= 0 && v <= 1)
                      algo = (int32_t)v;
                    else if (err == 0) err = 3;
                  }
                } else {
                  int64_t v;
                  if (!c.integer(&v)) return fail();
                  if (v >= 0 && v <= 1) algo = (int32_t)v;
                  else if (err == 0) err = 3;
                }
              } else if (key_is(body, foff, flen, "behavior")) {
                c.ws();
                if (c.p < c.end && *c.p == '"') {
                  int64_t boff, blen2;
                  if (!c.str(&boff, &blen2, body)) return fail();
                  if (token_is(body, boff, blen2, "BATCHING")) behavior |= 0;
                  else if (token_is(body, boff, blen2, "NO_BATCHING")) behavior |= 1;
                  else if (token_is(body, boff, blen2, "GLOBAL")) behavior |= 2;
                  else if (token_is(body, boff, blen2, "DURATION_IS_GREGORIAN")) behavior |= 4;
                  else if (token_is(body, boff, blen2, "RESET_REMAINING")) behavior |= 8;
                  else if (token_is(body, boff, blen2, "MULTI_REGION")) behavior |= 16;
                  else {
                    JsonCursor t{body + boff, body + boff + blen2};
                    int64_t v;
                    if (t.integer(&v) && t.p == t.end) behavior = (int32_t)v;
                    else if (err == 0) err = 4;
                  }
                } else if (c.p < c.end && *c.p == '[') {
                  // list of flag names: rare — Python fallback
                  return fail();
                } else {
                  int64_t v;
                  if (!c.integer(&v)) return fail();
                  behavior = (int32_t)v;
                }
              } else {
                if (!c.skip_value()) return fail();
              }
              if (c.lit(',')) continue;
              if (c.lit('}')) break;
              return fail();
            }
          }
          // validation order matches gubernator.go:142-152 (unique_key first)
          if (err == 0 && ulen == 0) err = 1;
          if (err == 0 && nlen == 0) err = 2;
          jb->algo.push_back(algo);
          jb->behavior.push_back(behavior);
          jb->hits.push_back(hits);
          jb->limit.push_back(limit);
          jb->duration.push_back(duration);
          jb->err.push_back(err);
          jb->nspan.push_back(noff);
          jb->nspan.push_back(nlen);
          jb->ukspan.push_back(uoff);
          jb->ukspan.push_back(ulen);
          jb->hk.append(body + noff, (size_t)nlen);
          jb->hk.push_back('_');
          jb->hk.append(body + uoff, (size_t)ulen);
          if (c.lit(',')) continue;
          if (c.lit(']')) break;
          return fail();
        }
      }
    } else {
      if (!c.skip_value()) return fail();
    }
    if (c.lit(',')) continue;
    if (c.lit('}')) break;
    return fail();
  }
  c.ws();
  if (c.p != c.end || !c.ok || !found_requests) {
    if (!found_requests && c.ok && c.p == c.end) {
      jb->hkoff.push_back(0);
      return jb;  // no "requests" key: empty batch (gateway .get default)
    }
    return fail();
  }
  jb->hkoff.resize(jb->algo.size() + 1);
  int64_t acc = 0;
  for (size_t i = 0; i < jb->algo.size(); i++) {
    jb->hkoff[i] = acc;
    acc += jb->nspan[2 * i + 1] + 1 + jb->ukspan[2 * i + 1];
  }
  jb->hkoff[jb->algo.size()] = acc;
  return jb;
}

int64_t gt_json_n(void* j) { return (int64_t)((JsonBatch*)j)->algo.size(); }
int64_t gt_json_hk_bytes(void* j) { return (int64_t)((JsonBatch*)j)->hk.size(); }

void gt_json_fill(void* jv, int32_t* algo, int32_t* behavior, int64_t* hits,
                  int64_t* limit, int64_t* duration, uint8_t* err, char* hk,
                  int64_t* hkoff, int64_t* nspan, int64_t* ukspan) {
  auto* j = (JsonBatch*)jv;
  size_t n = j->algo.size();
  if (n) {
    memcpy(algo, j->algo.data(), n * sizeof(int32_t));
    memcpy(behavior, j->behavior.data(), n * sizeof(int32_t));
    memcpy(hits, j->hits.data(), n * sizeof(int64_t));
    memcpy(limit, j->limit.data(), n * sizeof(int64_t));
    memcpy(duration, j->duration.data(), n * sizeof(int64_t));
    memcpy(err, j->err.data(), n);
    memcpy(nspan, j->nspan.data(), 2 * n * sizeof(int64_t));
    memcpy(ukspan, j->ukspan.data(), 2 * n * sizeof(int64_t));
  }
  if (!j->hk.empty()) memcpy(hk, j->hk.data(), j->hk.size());
  memcpy(hkoff, j->hkoff.data(), (n + 1) * sizeof(int64_t));
}

void gt_json_free(void* j) { delete (JsonBatch*)j; }

// Render the GetRateLimits response body from result columns.  Lanes
// listed in ov_idx (sorted) splice in pre-rendered JSON objects
// (validation errors / forwarded lanes — rendered by Python, which
// keeps full metadata fidelity).  Single pass straight into the
// caller's buffer; `cap` must hold the worst case (a per-lane object
// is <= 129 bytes: 58 fixed + 11 status + 3x20 digits — callers
// budget 160).  Returns bytes written, or -1 if cap would overflow.
int64_t gt_json_render(const int32_t* status, const int64_t* limit,
                       const int64_t* remaining, const int64_t* reset,
                       int64_t n, const int64_t* ov_idx, int64_t n_ov,
                       const char* ov_buf, const int64_t* ov_off,
                       char* out, int64_t cap) {
  static const char* kStatus[] = {"UNDER_LIMIT", "OVER_LIMIT"};
  char* w = out;
  char* wend = out + cap;
  auto put = [&](const char* p, size_t len) {
    if (w + len > wend) return false;
    memcpy(w, p, len);
    w += len;
    return true;
  };
  auto lit = [&](const char* p) { return put(p, strlen(p)); };
  if (!lit("{\"responses\":[")) return -1;
  int64_t oi = 0;
  char tmp[24];
  for (int64_t i = 0; i < n; i++) {
    if (i && !lit(",")) return -1;
    if (oi < n_ov && ov_idx[oi] == i) {
      if (!put(ov_buf + ov_off[oi], (size_t)(ov_off[oi + 1] - ov_off[oi])))
        return -1;
      oi++;
      continue;
    }
    if (!lit("{\"status\":\"") || !lit(kStatus[status[i] & 1]) ||
        !lit("\",\"limit\":\"") ||
        !put(tmp, snprintf(tmp, sizeof tmp, "%lld", (long long)limit[i])) ||
        !lit("\",\"remaining\":\"") ||
        !put(tmp, snprintf(tmp, sizeof tmp, "%lld", (long long)remaining[i])) ||
        !lit("\",\"resetTime\":\"") ||
        !put(tmp, snprintf(tmp, sizeof tmp, "%lld", (long long)reset[i])) ||
        !lit("\"}"))
      return -1;
  }
  if (!lit("]}")) return -1;
  return (int64_t)(w - out);
}

}  // extern "C"

// ======================================================================
// GUBC ingress-frame parser (gt_frame_*): the public columnar front
// door's decode half in C++.
//
// A kind-5 ingress frame (wire.py "public columnar ingress") arrives
// through the epoll edge below; before any Python-level work runs, one
// native pass — entered via ctypes with the GIL released — validates
// the whole frame (magic/version/kind, string-column offset
// monotonicity, section lengths, algorithm range), computes the byte
// position of every column so Python wraps them as zero-copy numpy
// views, builds the packed hash keys (name + '_' + unique_key — the
// planner's input) with one scatter, and stamps per-lane validation
// codes (1 = empty unique_key, 2 = empty name; gubernator.go:142-152
// order).  The GIL only ever sees ready column buffers.  Anything
// malformed returns NULL and the numpy decode path reproduces the
// exact error wording.
//
// The scatter runs on the WORKER thread (parallel across workers,
// GIL-free), not the epoll thread: the epoll loop is the one shared
// resource every connection serializes on, so per-frame O(bytes) work
// there would re-create the convoy this edge exists to remove.
// ======================================================================

namespace {

struct FrameBatch {
  const char* body;  // caller-owned; must outlive the handle
  int64_t n = 0;
  int64_t name_off_pos = 0, name_blob_pos = 0, name_blob_len = 0;
  int64_t uk_off_pos = 0, uk_blob_pos = 0, uk_blob_len = 0;
};

// Little-endian u32 at an arbitrary (possibly unaligned) offset.
inline uint32_t frame_u32(const char* p) {
  uint32_t v;
  memcpy(&v, p, 4);
  return v;
}

// Validate one string column at `pos`; fills off_pos/blob_pos/blob_len
// and returns the position past the column, or -1 when malformed
// (truncated, non-zero first offset, non-monotonic, length mismatch —
// the same checks wire._read_str_blob makes).
int64_t frame_str_col(const char* body, int64_t blen, int64_t pos, int64_t n,
                      int64_t* off_pos, int64_t* blob_pos, int64_t* blob_len) {
  if (pos + 4 > blen) return -1;
  int64_t bl = (int64_t)frame_u32(body + pos);
  pos += 4;
  if (pos + 4 * (n + 1) > blen) return -1;
  *off_pos = pos;
  const char* off = body + pos;
  pos += 4 * (n + 1);
  if (pos + bl > blen) return -1;
  if (n) {
    if (frame_u32(off) != 0) return -1;
    uint32_t prev = 0;
    for (int64_t i = 1; i <= n; i++) {
      uint32_t cur = frame_u32(off + 4 * i);
      if (cur < prev) return -1;
      prev = cur;
    }
    if ((int64_t)prev != bl) return -1;
  }
  *blob_pos = pos;
  *blob_len = bl;
  return pos + bl;
}

}  // namespace

extern "C" {

typedef struct {
  int64_t n;
  int64_t name_off_pos, name_blob_pos;
  int64_t uk_off_pos, uk_blob_pos;
  int64_t algo_pos, beh_pos, hits_pos, limit_pos, dur_pos;
  int64_t trace_pos;    // byte offset of the GTRC magic, -1 = absent
  int64_t trace_count;  // trailer entry count (32 bytes each)
  int64_t hk_bytes;     // packed hash-key buffer size for gt_frame_fill
} GtFrameInfo;

// Parse + validate a GUBC request frame of `kind`; fills *out and
// returns a handle for gt_frame_fill/gt_frame_free, or NULL when the
// frame is malformed (caller falls back to the Python decode for the
// exact error).  `body` must stay valid until gt_frame_free.
void* gt_frame_parse(const char* body, int64_t blen, int32_t kind,
                     GtFrameInfo* out) {
  if (blen < 10 || memcmp(body, "GUBC", 4) != 0) return nullptr;
  if ((uint8_t)body[4] != 1 || (uint8_t)body[5] != (uint8_t)kind)
    return nullptr;
  int64_t n = (int64_t)frame_u32(body + 6);
  // 2M lanes is far past every cap (PEER_COLUMNS_MAX_LANES = 16384);
  // bounding n keeps the size arithmetic below trivially overflow-free.
  if (n > (int64_t)2 * 1024 * 1024) return nullptr;
  FrameBatch fb;
  fb.body = body;
  fb.n = n;
  int64_t pos = 10;
  pos = frame_str_col(body, blen, pos, n, &fb.name_off_pos,
                      &fb.name_blob_pos, &fb.name_blob_len);
  if (pos < 0) return nullptr;
  pos = frame_str_col(body, blen, pos, n, &fb.uk_off_pos, &fb.uk_blob_pos,
                      &fb.uk_blob_len);
  if (pos < 0) return nullptr;
  if (pos + n * (4 + 4 + 8 + 8 + 8) > blen) return nullptr;
  out->algo_pos = pos;
  pos += 4 * n;
  out->beh_pos = pos;
  pos += 4 * n;
  out->hits_pos = pos;
  pos += 8 * n;
  out->limit_pos = pos;
  pos += 8 * n;
  out->dur_pos = pos;
  pos += 8 * n;
  // Algorithm range check (the public edge's one semantic column
  // check): out-of-range values reject the frame before the kernel
  // could see a garbage branch selector.
  for (int64_t i = 0; i < n; i++) {
    int32_t a;
    memcpy(&a, body + out->algo_pos + 4 * i, 4);
    if (a < 0 || a > 1) return nullptr;
  }
  out->trace_pos = -1;
  out->trace_count = 0;
  if (pos != blen) {
    // Only legal continuation: the GTRC trace trailer (wire.py).
    if (pos + 8 > blen || memcmp(body + pos, "GTRC", 4) != 0) return nullptr;
    out->trace_pos = pos;
    int64_t count = (int64_t)frame_u32(body + pos + 4);
    if (pos + 8 + count * 32 != blen) return nullptr;
    out->trace_count = count;
  }
  out->n = n;
  out->name_off_pos = fb.name_off_pos;
  out->name_blob_pos = fb.name_blob_pos;
  out->uk_off_pos = fb.uk_off_pos;
  out->uk_blob_pos = fb.uk_blob_pos;
  out->hk_bytes = fb.name_blob_len + n + fb.uk_blob_len;
  return new FrameBatch(fb);
}

// Build the packed hash keys (hk u8[hk_bytes] + hkoff i64[n+1]) and
// per-lane validation codes (err u8[n]: 1 empty unique_key, 2 empty
// name) from the frame the handle was parsed over.
void gt_frame_fill(void* h, uint8_t* hk, int64_t* hkoff, uint8_t* err) {
  auto* fb = (FrameBatch*)h;
  const char* body = fb->body;
  const char* noff = body + fb->name_off_pos;
  const char* uoff = body + fb->uk_off_pos;
  const char* nblob = body + fb->name_blob_pos;
  const char* ublob = body + fb->uk_blob_pos;
  int64_t w = 0;
  for (int64_t i = 0; i < fb->n; i++) {
    hkoff[i] = w;
    uint32_t n0 = frame_u32(noff + 4 * i), n1 = frame_u32(noff + 4 * (i + 1));
    uint32_t u0 = frame_u32(uoff + 4 * i), u1 = frame_u32(uoff + 4 * (i + 1));
    size_t nlen = n1 - n0, ulen = u1 - u0;
    memcpy(hk + w, nblob + n0, nlen);
    w += nlen;
    hk[w++] = '_';
    memcpy(hk + w, ublob + u0, ulen);
    w += ulen;
    err[i] = ulen == 0 ? 1 : (nlen == 0 ? 2 : 0);
  }
  hkoff[fb->n] = w;
}

void gt_frame_free(void* h) { delete (FrameBatch*)h; }

}  // extern "C"

// ======================================================================
// Native HTTP/1.1 edge (gt_http_*): the gateway's socket + framing
// layer in C++.
//
// The measured cost of the stdlib gateway (benchmarks/RESULTS.md cfg8
// decomposition) is ~1.1 ms/request of Python HTTP parsing plus a
// thread-per-connection model that convoys at 100-way concurrency on
// the GIL.  This edge replaces exactly that layer: N ACCEPTOR threads
// (GUBER_ACCEPTORS, SO_REUSEPORT — the kernel shards accepted
// connections across the group, so one serializing epoll loop stops
// being the ingress ceiling once the fast lane below removes Python
// from the per-frame path) each own accept/read/frame/write for their
// connections; parsed requests (method, path, body) queue to Python
// worker threads via gt_http_next (ctypes releases the GIL while they
// block), which run the UNCHANGED service path and hand response bytes
// back via gt_http_respond.  An optional AF_UNIX acceptor
// (GUBER_UDS_PATH) serves the same HTTP/1.1 + GUBC frames to same-host
// clients — the sidecar deployment the reference's k8s manifests imply
// — with zero TCP stack cost.  The reference serves its edge from
// compiled code too (the Go http runtime, daemon.go:194-239) — this is
// that capability, not a new protocol: same endpoints, same JSON, same
// errors.
//
// Idle behavior: each acceptor's epoll_wait blocks INDEFINITELY unless
// it owes a stall-sweep tick (an EOF'd conn with staged unread output)
// — response staging and shutdown wake it through its eventfd — so an
// idle daemon with N acceptors costs zero periodic wakeups instead of
// N x 5/s.
//
// Scope: HTTP/1.1 keep-alive, Content-Length bodies (no chunked
// REQUESTS — no client of this API sends them), no TLS (the daemon
// keeps the Python+ssl gateway when TLS is configured).  Bounded
// header/body sizes and a bounded ready queue (overflow answers 503
// without touching Python).
// ======================================================================

#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <sys/un.h>
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <deque>
#include <memory>
#include <mutex>
#include <thread>
#include <vector>

namespace {

constexpr size_t kMaxHeaderBytes = 64 * 1024;
constexpr size_t kMaxBodyBytes = 32 * 1024 * 1024;  // > 1000-lane batches
constexpr size_t kMaxReadyQueue = 4096;
// Answers whose last byte the kernel has accepted, kept until Python
// drains them (gt_http_drain_sends); the oldest is dropped beyond this.
constexpr size_t kMaxSendRing = 4096;

// The edge's clock: steady_clock is CLOCK_MONOTONIC here, the clock of
// Python's time.monotonic_ns() (gt_mono_ns lets a test hold that), so a
// stamp taken in C++ subtracts from one taken in Python.
inline int64_t ns_of(std::chrono::steady_clock::time_point t) {
  return (int64_t)std::chrono::duration_cast<std::chrono::nanoseconds>(
             t.time_since_epoch())
      .count();
}
inline int64_t mono_ns() { return ns_of(std::chrono::steady_clock::now()); }

struct HttpServer;
struct HttpAcceptor;

struct HttpPending {
  uint64_t token;
  int fd;
  int acceptor;  // index into HttpServer::acceptors
  int method;    // 0 GET, 1 POST, 2 other
  bool keep_alive;
  std::string path;
  std::string body;
  // Stamps of the edge (mono_ns): the read that brought the request's
  // first byte, and the moment its last body byte was framed.
  int64_t t_first_byte = 0, t_body = 0;
};

// One finished response on its way out: the bytes, and when they were
// handed to the acceptor (0 = answered inside the loop: no send record).
struct HttpResponse {
  std::string bytes;
  int64_t t_staged = 0;
};

// Where one response ends inside HttpConn::out, so that the send which
// passes `end` can stamp that response's last byte.
struct HttpOutMark {
  size_t end;
  uint64_t token;
  int64_t t_staged;
};

// (token, t_staged, t_last_byte) of one answered request: edge.send.
struct HttpSendRec {
  uint64_t token;
  int64_t t_staged, t_last_byte;
};

// A read of one wakeup: the bytes of HttpConn::in up to `end` had
// arrived by `t`.
struct HttpReadStamp {
  size_t end;
  int64_t t;
};

struct HttpConn {
  int fd = -1;
  HttpAcceptor* acc = nullptr;
  std::string in;
  // The read that brought the first byte `in` holds (the head request's
  // t_first_byte); meaningless while `in` is empty.
  int64_t t_first_byte = 0;
  // parsed-but-unanswered request count (pipelined clients): responses
  // write in arrival order because tokens are handed out in order and
  // the out buffer is appended in respond order per connection --
  // workers MAY finish out of order, so per-conn ordering is enforced
  // by queueing responses by token sequence.
  std::deque<uint64_t> awaiting;          // tokens awaiting response
  std::unordered_map<uint64_t, HttpResponse> done;  // token -> response
  std::string out;
  size_t out_off = 0;
  std::deque<HttpOutMark> marks;  // ends of the responses `out` holds

  bool want_close = false;
  // Read side hit EOF (client close or shutdown(SHUT_WR)): stop
  // watching EPOLLIN — level-triggered EOF would otherwise re-fire
  // every epoll_wait and spin the loop while responses are pending.
  bool saw_eof = false;
  // Write-stall clock for EOF'd conns with staged output: a peer that
  // half-closed and never reads would otherwise pin the fd + buffer
  // forever (no EPOLLIN events, EPOLLOUT never re-fires past a full
  // sndbuf).  Zero = not stalled; reset on write progress.
  std::chrono::steady_clock::time_point stall_start{};
};

// One listener + one epoll loop.  A REUSEPORT group is N of these on
// the same TCP port; the optional UDS lane is one more.  Connection
// state (conns map, response queue, stats) is guarded by the server's
// shared mutex — cross-thread response staging (Python workers, the
// fast-lane completion) must reach any acceptor — but each loop only
// ever TOUCHES its own conns, so the hot read/write path contends on
// the lock only at stage/close boundaries.
struct HttpAcceptor {
  HttpServer* srv = nullptr;
  int idx = 0;
  bool is_uds = false;
  int listen_fd = -1, epfd = -1, evfd = -1;
  std::thread loop;
  std::unordered_map<int, HttpConn*> conns;  // guarded by srv->mu
  // responses staged by Python / the fast lane, drained by this loop
  std::deque<std::pair<uint64_t, HttpResponse>> resp_queue;  // srv->mu
  // stats (guarded by srv->mu): the per-acceptor fairness surface
  // (gubernator_ingress_acceptor_*).
  int64_t accepted = 0, requests = 0, ingress_frames = 0,
          ingress_lanes = 0, wakeups = 0;
  // The socket's own work (srv->mu; the loop counts on its own and
  // folds in once a wakeup): `/debug/status` `edge`.
  int64_t reads = 0, read_bytes = 0, sends = 0, send_bytes = 0,
          epollout_rounds = 0;
};

struct HttpServer {
  std::vector<std::unique_ptr<HttpAcceptor>> acceptors;
  int port = 0;
  std::string uds_path;
  std::atomic<bool> stopping{false};

  std::mutex mu;
  std::condition_variable cv;
  std::deque<HttpPending*> ready;                  // parsed, for Python
  std::unordered_map<uint64_t, HttpPending*> inflight;  // token -> req
  // token -> (acceptor idx, fd): which conn answers the token.
  std::unordered_map<uint64_t, std::pair<int, int>> token_addr;
  uint64_t next_token = 1;
  // Answered requests not yet drained by Python (bounded: kMaxSendRing).
  // `send_ring_n` is its depth, written under mu and read without it, so
  // that a drain of an empty ring takes no lock.
  std::deque<HttpSendRec> send_ring;
  std::atomic<int64_t> send_ring_n{0};
  int64_t send_ring_dropped = 0;
};

void http_close_conn(HttpServer* s, HttpConn* c) {
  HttpAcceptor* a = c->acc;
  epoll_ctl(a->epfd, EPOLL_CTL_DEL, c->fd, nullptr);
  close(c->fd);
  {
    // Tokens of this connection that are still inflight must not write
    // to a reused fd: drop the mapping (responses get discarded).
    std::lock_guard<std::mutex> lk(s->mu);
    for (uint64_t t : c->awaiting) s->token_addr.erase(t);
    a->conns.erase(c->fd);
  }
  delete c;
}

void http_arm(HttpConn* c) {
  epoll_event ev{};
  ev.data.fd = c->fd;
  ev.events = (c->saw_eof ? 0u : EPOLLIN) |
              (c->out.size() > c->out_off ? EPOLLOUT : 0u);
  epoll_ctl(c->acc->epfd, EPOLL_CTL_MOD, c->fd, &ev);
}

// THE HTTP/1.1 response envelope of this edge — gt_http_respond, the
// ingress fast lane's kind-6/shed/error/shutdown fills and the Python
// edge's byte-identity contract all share this one builder, so a
// header change cannot silently fork the golden-tested response shape.
std::string http_envelope(int status, const char* reason,
                          const char* ctype, const char* body,
                          int64_t blen) {
  std::string r = "HTTP/1.1 " + std::to_string(status) + " " +
                  (reason && *reason ? reason : "OK") +
                  "\r\nContent-Type: " +
                  (ctype && *ctype ? ctype : "application/json") +
                  "\r\nContent-Length: " + std::to_string(blen) +
                  "\r\n\r\n";
  r.append(body, (size_t)blen);
  return r;
}

std::string http_simple_response(int code, const char* reason,
                                 const std::string& body, bool keep_alive) {
  std::string r = "HTTP/1.1 " + std::to_string(code) + " " + reason +
                  "\r\nContent-Type: application/json\r\nContent-Length: " +
                  std::to_string(body.size()) + "\r\n";
  if (!keep_alive) r += "Connection: close\r\n";
  r += "\r\n";
  r += body;
  return r;
}

// Stage one finished response onto its connection's acceptor queue and
// wake that loop.  The shared exit of gt_http_respond and the ingress
// fast lane's native response fill.
void http_stage_response(HttpServer* s, uint64_t token, std::string resp) {
  int64_t t_staged = mono_ns();
  std::lock_guard<std::mutex> lk(s->mu);
  auto it = s->token_addr.find(token);
  if (it == s->token_addr.end()) return;  // conn died
  HttpAcceptor* a = s->acceptors[(size_t)it->second.first].get();
  a->resp_queue.emplace_back(token, HttpResponse{std::move(resp), t_staged});
  // After shutdown the eventfd is closed (and its number may be
  // reused elsewhere in the process) — never write it while
  // stopping.  Checked and written under s->mu: gt_http_shutdown
  // closes the fds under the same lock after setting stopping, so a
  // false read here guarantees the fd is still ours.
  if (!s->stopping.load()) {
    uint64_t one_u = 1;
    (void)!write(a->evfd, &one_u, 8);
  }
}

// Flush completed responses (in token order) into the conn's out buffer.
void http_stage_done(HttpConn* c) {
  while (!c->awaiting.empty()) {
    auto it = c->done.find(c->awaiting.front());
    if (it == c->done.end()) break;
    c->out += it->second.bytes;
    if (it->second.t_staged) {
      c->marks.push_back({c->out.size(), it->first, it->second.t_staged});
    }
    c->done.erase(it);
    c->awaiting.pop_front();
  }
}

// Parse as many complete requests as the buffer holds.  Returns false
// when the connection must die (malformed / oversize).  `reads` are this
// wakeup's reads: a pipelined request whose first bytes arrived with its
// predecessor's tail takes the time of the read that brought them.
bool http_drain_input(HttpServer* s, HttpConn* c, const HttpReadStamp* reads,
                      int n_reads) {
  size_t consumed = 0;  // bytes of `in`, as the reads saw it, framed so far
  for (;;) {
    size_t he = c->in.find("\r\n\r\n");
    if (he == std::string::npos) {
      return c->in.size() <= kMaxHeaderBytes;
    }
    std::string_view head(c->in.data(), he);
    size_t line_end = head.find("\r\n");
    std::string_view req_line =
        head.substr(0, line_end == std::string_view::npos ? he : line_end);
    int method = 2;
    size_t path_off = 0;
    if (req_line.rfind("GET ", 0) == 0) { method = 0; path_off = 4; }
    else if (req_line.rfind("POST ", 0) == 0) { method = 1; path_off = 5; }
    if (method == 2) {
      if (req_line.find(' ') == std::string_view::npos) return false;
      // Parseable frame, unsupported method (HEAD/OPTIONS/PUT...):
      // answer 501 and close — a silent reset would make e.g. HEAD
      // health probes read as a hard backend failure.
      uint64_t t;
      {
        std::lock_guard<std::mutex> lk(s->mu);
        t = s->next_token++;
        c->awaiting.push_back(t);
      }
      c->done[t].bytes = http_simple_response(
          501, "Not Implemented",
          "{\"code\": 12, \"message\": \"method not implemented\"}", false);
      http_stage_done(c);
      c->want_close = true;
      c->in.clear();
      return true;
    }
    size_t path_end = req_line.find(' ', path_off);
    if (path_end == std::string_view::npos) return false;
    std::string path(req_line.substr(path_off, path_end - path_off));

    size_t content_len = 0;
    bool keep_alive = true;  // HTTP/1.1 default
    // header scan (case-insensitive names)
    size_t pos = (line_end == std::string_view::npos) ? he : line_end + 2;
    while (pos < he) {
      size_t eol = head.find("\r\n", pos);
      std::string_view line =
          head.substr(pos, (eol == std::string_view::npos ? he : eol) - pos);
      size_t colon = line.find(':');
      if (colon != std::string_view::npos) {
        std::string name(line.substr(0, colon));
        for (auto& ch : name) ch = (char)tolower((unsigned char)ch);
        std::string_view val = line.substr(colon + 1);
        while (!val.empty() && val.front() == ' ') val.remove_prefix(1);
        if (name == "content-length") {
          content_len = strtoull(std::string(val).c_str(), nullptr, 10);
        } else if (name == "connection") {
          std::string v(val);
          for (auto& ch : v) ch = (char)tolower((unsigned char)ch);
          if (v.find("close") != std::string::npos) keep_alive = false;
        }
      }
      if (eol == std::string_view::npos) break;
      pos = eol + 2;
    }
    if (content_len > kMaxBodyBytes) return false;
    size_t total = he + 4 + content_len;
    if (c->in.size() < total) return true;  // need more body bytes

    auto* p = new HttpPending;
    p->fd = c->fd;
    p->acceptor = c->acc->idx;
    p->method = method;
    p->keep_alive = keep_alive;
    p->path = std::move(path);
    p->t_first_byte = c->t_first_byte;
    p->t_body = mono_ns();
    p->body.assign(c->in, he + 4, content_len);
    c->in.erase(0, total);
    consumed += total;
    if (!c->in.empty() && n_reads > 0) {
      int i = 0;
      while (i < n_reads - 1 && reads[i].end <= consumed) ++i;
      c->t_first_byte = reads[i].t;
    }
    if (!keep_alive) c->want_close = true;

    std::unique_lock<std::mutex> lk(s->mu);
    p->token = s->next_token++;
    c->awaiting.push_back(p->token);
    ++c->acc->requests;
    if (s->ready.size() >= kMaxReadyQueue) {
      // Overload: answer 503 without touching Python — through the
      // ordered done-queue so pipelined responses never reorder.
      uint64_t t = p->token;
      lk.unlock();
      delete p;
      c->done[t].bytes = http_simple_response(
          503, "Service Unavailable",
          "{\"code\": 14, \"message\": \"ingress queue full\"}", keep_alive);
      http_stage_done(c);
      continue;
    }
    s->token_addr[p->token] = {c->acc->idx, c->fd};
    s->ready.push_back(p);
    lk.unlock();
    s->cv.notify_one();
  }
}

// An EOF'd peer gets this long to drain its staged response before the
// conn is reclaimed.  Generous on purpose: it exists to bound abuse
// (half-close, never read), not to race legitimate slow readers or the
// multi-tens-of-seconds device rounds a response may still be awaiting
// (the clock only runs while bytes are STAGED and unread).
constexpr auto kEofWriteStall = std::chrono::seconds(30);

void http_loop(HttpAcceptor* a) {
  HttpServer* s = a->srv;
  epoll_event evs[64];
  // Adaptive idle timeout: block indefinitely unless the previous
  // sweep found an EOF-stalled conn whose deadline needs the clock
  // (response staging and shutdown wake us via the eventfd, so the
  // block costs nothing in liveness; the old fixed 200 ms tick burned
  // idle CPU per acceptor once there were N loops).
  bool need_tick = false;
  // This wakeup's own count of the socket's work and the answers whose
  // last byte left in it: folded into the acceptor's counters and the
  // server's send ring under the sweep's lock hold, below.
  int64_t reads = 0, read_bytes = 0, sends = 0, send_bytes = 0,
          epollout_rounds = 0;
  std::vector<HttpSendRec> sent;
  for (;;) {
    int n = epoll_wait(a->epfd, evs, 64, need_tick ? 200 : -1);
    if (s->stopping.load()) return;
    // Stage responses staged since the last wake.
    {
      std::unique_lock<std::mutex> lk(s->mu);
      ++a->wakeups;
      while (!a->resp_queue.empty()) {
        auto [token, resp] = std::move(a->resp_queue.front());
        a->resp_queue.pop_front();
        auto tf = s->token_addr.find(token);
        if (tf == s->token_addr.end()) continue;  // conn died
        auto ci = a->conns.find(tf->second.second);
        s->token_addr.erase(tf);
        if (ci == a->conns.end()) continue;
        HttpConn* c = ci->second;
        c->done[token] = std::move(resp);
        lk.unlock();
        http_stage_done(c);
        http_arm(c);
        lk.lock();
      }
    }
    for (int i = 0; i < n; ++i) {
      int fd = evs[i].data.fd;
      if (fd == a->evfd) {
        uint64_t junk;
        (void)!read(a->evfd, &junk, 8);
        continue;
      }
      if (fd == a->listen_fd) {
        for (;;) {
          int cfd = accept4(a->listen_fd, nullptr, nullptr, SOCK_NONBLOCK);
          if (cfd < 0) break;
          if (!a->is_uds) {
            int one = 1;
            setsockopt(cfd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
          }
          auto* c = new HttpConn;
          c->fd = cfd;
          c->acc = a;
          {
            std::lock_guard<std::mutex> lk(s->mu);
            a->conns[cfd] = c;
            ++a->accepted;
          }
          epoll_event ev{};
          ev.data.fd = cfd;
          ev.events = EPOLLIN;
          epoll_ctl(a->epfd, EPOLL_CTL_ADD, cfd, &ev);
        }
        continue;
      }
      HttpConn* c;
      {
        std::lock_guard<std::mutex> lk(s->mu);
        auto it = a->conns.find(fd);
        if (it == a->conns.end()) continue;
        c = it->second;
      }
      bool dead = false;
      if (evs[i].events & (EPOLLHUP | EPOLLERR)) {
        dead = true;
      }
      if (!dead && (evs[i].events & EPOLLIN)) {
        char buf[65536];
        bool eof = false;
        // The reads of this wakeup, for the first byte of a pipelined
        // request; beyond the array's length the last entry stands for
        // every later read.
        HttpReadStamp stamps[32];
        int n_stamps = 0;
        for (;;) {
          ssize_t r = read(fd, buf, sizeof buf);
          if (r > 0) {
            int64_t t_read = mono_ns();
            if (c->in.empty()) c->t_first_byte = t_read;
            c->in.append(buf, (size_t)r);
            if (n_stamps < 32) ++n_stamps;
            stamps[n_stamps - 1] = {c->in.size(), t_read};
            ++reads;
            read_bytes += r;
            if (c->in.size() > kMaxHeaderBytes + kMaxBodyBytes) { dead = true; break; }
          } else if (r == 0) { eof = true; break; }
          else { if (errno != EAGAIN && errno != EWOULDBLOCK) dead = true; break; }
        }
        // Frame BEFORE honoring EOF: request bytes and the FIN often
        // arrive in one wakeup (a client that sends-and-closes, or
        // half-closes with shutdown(SHUT_WR) and still reads).  Killing
        // the conn on r==0 without draining would DROP fully-received
        // requests — observed as lost hits under load.
        if (!dead && !http_drain_input(s, c, stamps, n_stamps)) dead = true;
        if (!dead && eof) {
          // Half-close semantics: serve what was fully received, flush
          // any responses (the write side may still be open), then
          // close — the generic want_close check below fires once
          // everything is flushed, including on this same iteration
          // when nothing is pending.
          c->want_close = true;
          c->saw_eof = true;
        }
      }
      if (!dead && (evs[i].events & EPOLLOUT) && c->out.size() > c->out_off) {
        ++epollout_rounds;
        // MSG_NOSIGNAL: a peer that closed after its FIN must surface
        // as EPIPE, not SIGPIPE (Python ignores SIGPIPE; a non-Python
        // embedder would die).
        ssize_t w = send(fd, c->out.data() + c->out_off,
                         c->out.size() - c->out_off, MSG_NOSIGNAL);
        if (w > 0) {
          c->out_off += (size_t)w;
          ++sends;
          send_bytes += w;
          // The send that passes a response's end stamps its last byte:
          // the kernel has accepted it (not: the client has read it).
          if (!c->marks.empty() && c->marks.front().end <= c->out_off) {
            int64_t t_last_byte = mono_ns();
            do {
              const HttpOutMark& m = c->marks.front();
              sent.push_back({m.token, m.t_staged, t_last_byte});
              c->marks.pop_front();
            } while (!c->marks.empty() && c->marks.front().end <= c->out_off);
          }
          if (c->out_off == c->out.size()) { c->out.clear(); c->out_off = 0; }
          c->stall_start = {};  // progress: restart the stall clock
        } else if (w < 0 && errno != EAGAIN && errno != EWOULDBLOCK) {
          dead = true;
        }
      }
      if (!dead && c->want_close && c->awaiting.empty() && c->done.empty() &&
          c->out.size() == c->out_off) {
        dead = true;  // graceful close after the last response flushed
      }
      if (dead) http_close_conn(s, c);
      else http_arm(c);
    }
    {
      // Reclaim EOF'd conns whose peer stopped reading (see
      // HttpConn::stall_start).  O(conns) each wakeup; while any such
      // conn exists the loop keeps a 200 ms tick (need_tick), and
      // blocks indefinitely otherwise.
      //
      // Runs AFTER the fetched event batch above, never before: a
      // sweep close ahead of the loop would free an fd whose events
      // are still queued in evs[], and an accept() later in the SAME
      // batch can return that fd number for a brand-new conn — the
      // stale EPOLLHUP/EPOLLERR entry would then kill the reused fd
      // (round-5 advisor finding).  Sweeping here means every event
      // consumed belongs to the conn it was fetched for, and any
      // write progress in this batch has already reset stall_start
      // before the deadline check.
      auto now = std::chrono::steady_clock::now();
      std::vector<HttpConn*> stalled;
      need_tick = false;
      {
        std::lock_guard<std::mutex> lk(s->mu);
        a->reads += reads;
        a->read_bytes += read_bytes;
        a->sends += sends;
        a->send_bytes += send_bytes;
        a->epollout_rounds += epollout_rounds;
        reads = read_bytes = sends = send_bytes = epollout_rounds = 0;
        for (const HttpSendRec& r : sent) {
          if (s->send_ring.size() >= kMaxSendRing) {
            s->send_ring.pop_front();
            ++s->send_ring_dropped;
          }
          s->send_ring.push_back(r);
        }
        sent.clear();
        s->send_ring_n.store((int64_t)s->send_ring.size(),
                             std::memory_order_release);
        for (auto& [fd, c] : a->conns) {
          if (!c->saw_eof || c->out.size() <= c->out_off) continue;
          if (c->stall_start == std::chrono::steady_clock::time_point{}) {
            c->stall_start = now;
            need_tick = true;
          } else if (now - c->stall_start > kEofWriteStall) {
            stalled.push_back(c);
          } else {
            need_tick = true;
          }
        }
      }
      for (auto* c : stalled) http_close_conn(s, c);
    }
  }
}

void http_destroy_acceptors(HttpServer* s) {
  for (auto& a : s->acceptors) {
    if (a->listen_fd >= 0) close(a->listen_fd);
    if (a->epfd >= 0) close(a->epfd);
    if (a->evfd >= 0) close(a->evfd);
  }
  if (!s->uds_path.empty()) unlink(s->uds_path.c_str());
}

}  // namespace

extern "C" {

typedef struct {
  uint64_t token;
  int32_t method;
  int32_t path_len;
  int64_t body_len;
  const char* path;
  const char* body;
  int64_t t_first_byte_ns;  // the edge's stamps (mono_ns), see HttpPending
  int64_t t_body_ns;
} GtHttpReq;

// Start the edge: `n_acceptors` SO_REUSEPORT TCP listeners on
// host:port (1 = the classic single loop, no REUSEPORT needed), plus
// one AF_UNIX listener at `uds_path` when non-empty (same HTTP/1.1 +
// frame protocol; a stale socket file is unlinked first — the daemon
// owns its configured path).  Returns NULL when any bind fails.
void* gt_http_start(const char* host, int port, int n_acceptors,
                    const char* uds_path) {
  auto* s = new HttpServer;
  if (n_acceptors < 1) n_acceptors = 1;
  int bound_port = port;
  for (int i = 0; i < n_acceptors; ++i) {
    auto a = std::make_unique<HttpAcceptor>();
    a->srv = s;
    a->idx = i;
    a->listen_fd = socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
    int one = 1;
    setsockopt(a->listen_fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof one);
    if (n_acceptors > 1) {
#ifdef SO_REUSEPORT
      if (setsockopt(a->listen_fd, SOL_SOCKET, SO_REUSEPORT, &one,
                     sizeof one) != 0) {
        close(a->listen_fd);
        http_destroy_acceptors(s);
        delete s;
        return nullptr;
      }
#else
      close(a->listen_fd);
      http_destroy_acceptors(s);
      delete s;
      return nullptr;
#endif
    }
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_port = htons((uint16_t)bound_port);
    addr.sin_addr.s_addr =
        host && *host ? inet_addr(host) : htonl(INADDR_LOOPBACK);
    if (bind(a->listen_fd, (sockaddr*)&addr, sizeof addr) != 0 ||
        listen(a->listen_fd, 512) != 0) {
      close(a->listen_fd);
      http_destroy_acceptors(s);
      delete s;
      return nullptr;
    }
    if (i == 0) {
      // Port 0 resolves at the first bind; the rest of the REUSEPORT
      // group binds the resolved port.
      socklen_t alen = sizeof addr;
      getsockname(a->listen_fd, (sockaddr*)&addr, &alen);
      bound_port = ntohs(addr.sin_port);
      s->port = bound_port;
    }
    s->acceptors.push_back(std::move(a));
  }
  if (uds_path && *uds_path) {
    sockaddr_un ua{};
    if (strlen(uds_path) >= sizeof ua.sun_path) {
      http_destroy_acceptors(s);
      delete s;
      return nullptr;
    }
    auto a = std::make_unique<HttpAcceptor>();
    a->srv = s;
    a->idx = (int)s->acceptors.size();
    a->is_uds = true;
    a->listen_fd = socket(AF_UNIX, SOCK_STREAM | SOCK_NONBLOCK, 0);
    ua.sun_family = AF_UNIX;
    strncpy(ua.sun_path, uds_path, sizeof ua.sun_path - 1);
    unlink(uds_path);  // the daemon owns its configured path
    if (bind(a->listen_fd, (sockaddr*)&ua, sizeof ua) != 0 ||
        listen(a->listen_fd, 512) != 0) {
      close(a->listen_fd);
      http_destroy_acceptors(s);
      delete s;
      return nullptr;
    }
    s->uds_path = uds_path;
    s->acceptors.push_back(std::move(a));
  }
  for (auto& a : s->acceptors) {
    a->epfd = epoll_create1(0);
    a->evfd = eventfd(0, EFD_NONBLOCK);
    epoll_event ev{};
    ev.data.fd = a->listen_fd;
    ev.events = EPOLLIN;
    epoll_ctl(a->epfd, EPOLL_CTL_ADD, a->listen_fd, &ev);
    ev.data.fd = a->evfd;
    ev.events = EPOLLIN;
    epoll_ctl(a->epfd, EPOLL_CTL_ADD, a->evfd, &ev);
  }
  for (auto& a : s->acceptors) {
    a->loop = std::thread(http_loop, a.get());
  }
  return s;
}

int gt_http_port(void* sv) { return ((HttpServer*)sv)->port; }

int gt_http_acceptor_count(void* sv) {
  return (int)((HttpServer*)sv)->acceptors.size();
}

// Per-acceptor stats: out is i64[count * 7] rows of {is_uds, accepted
// conns, requests, ingress frames (fast lane), ingress lanes, epoll
// wakeups, live conns}.
void gt_http_acceptor_stats(void* sv, int64_t* out) {
  auto* s = (HttpServer*)sv;
  std::lock_guard<std::mutex> lk(s->mu);
  for (size_t i = 0; i < s->acceptors.size(); ++i) {
    HttpAcceptor* a = s->acceptors[i].get();
    out[i * 7 + 0] = a->is_uds ? 1 : 0;
    out[i * 7 + 1] = a->accepted;
    out[i * 7 + 2] = a->requests;
    out[i * 7 + 3] = a->ingress_frames;
    out[i * 7 + 4] = a->ingress_lanes;
    out[i * 7 + 5] = a->wakeups;
    out[i * 7 + 6] = (int64_t)a->conns.size();
  }
}

// The socket's own work, summed over the acceptors (`/debug/status`
// `edge`): out is i64[7] = {reads, read bytes, sends, send bytes,
// EPOLLOUT rounds, requests, answered requests the send ring dropped
// before Python drained them (edge.send is under-observed by that
// many)}.  All cumulative, and all written under mu (the acceptor's own
// counts are folded in under its sweep's lock hold).
void gt_http_stats(void* sv, int64_t* out) {
  auto* s = (HttpServer*)sv;
  std::lock_guard<std::mutex> lk(s->mu);
  for (int i = 0; i < 7; ++i) out[i] = 0;
  for (auto& a : s->acceptors) {
    out[0] += a->reads;
    out[1] += a->read_bytes;
    out[2] += a->sends;
    out[3] += a->send_bytes;
    out[4] += a->epollout_rounds;
    out[5] += a->requests;
  }
  out[6] = s->send_ring_dropped;
}

// Hand Python the answered requests since its last call, oldest first:
// out is i64[cap * 3] rows of {token, t_staged, t_last_byte}.  Returns the
// rows written; what does not fit waits for the next call.
int64_t gt_http_drain_sends(void* sv, int64_t* out, int64_t cap) {
  auto* s = (HttpServer*)sv;
  if (s->send_ring_n.load(std::memory_order_acquire) == 0) return 0;
  std::lock_guard<std::mutex> lk(s->mu);
  int64_t n = 0;
  while (n < cap && !s->send_ring.empty()) {
    const HttpSendRec& r = s->send_ring.front();
    out[n * 3 + 0] = (int64_t)r.token;
    out[n * 3 + 1] = r.t_staged;
    out[n * 3 + 2] = r.t_last_byte;
    s->send_ring.pop_front();
    ++n;
  }
  s->send_ring_n.store((int64_t)s->send_ring.size(),
                       std::memory_order_release);
  return n;
}

// The edge's clock, for the test that holds it to time.monotonic_ns().
int64_t gt_mono_ns(void) { return mono_ns(); }

// Blocks (GIL released by ctypes) until a request is ready, the server
// stops (-1), or timeout_ms elapses (0).  1 = *out filled; pointers
// stay valid until gt_http_respond/gt_ingress_submit for that token.
int gt_http_next(void* sv, int64_t timeout_ms, GtHttpReq* out) {
  auto* s = (HttpServer*)sv;
  std::unique_lock<std::mutex> lk(s->mu);
  if (!s->cv.wait_for(lk, std::chrono::milliseconds(timeout_ms),
                      [&] { return !s->ready.empty() || s->stopping.load(); })) {
    return 0;
  }
  if (s->ready.empty()) return -1;  // stopping
  HttpPending* p = s->ready.front();
  s->ready.pop_front();
  s->inflight[p->token] = p;
  out->token = p->token;
  out->method = p->method;
  out->path_len = (int32_t)p->path.size();
  out->body_len = (int64_t)p->body.size();
  out->path = p->path.c_str();
  out->body = p->body.data();
  out->t_first_byte_ns = p->t_first_byte;
  out->t_body_ns = p->t_body;
  return 1;
}

void gt_http_respond(void* sv, uint64_t token, int status, const char* reason,
                     const char* ctype, const char* body, int64_t body_len) {
  auto* s = (HttpServer*)sv;
  std::string resp = http_envelope(status, reason, ctype, body, body_len);
  {
    std::lock_guard<std::mutex> lk(s->mu);
    auto it = s->inflight.find(token);
    if (it != s->inflight.end()) {
      delete it->second;
      s->inflight.erase(it);
    }
  }
  http_stage_response(s, token, std::move(resp));
}

// Two-phase teardown (shutdown -> free): workers may still be blocked
// in gt_http_next or finishing a long device round that will call
// gt_http_respond — the HttpServer must stay allocated until every
// worker has returned.  gt_http_shutdown stops traffic and joins the
// epoll threads; the caller joins its workers; gt_http_free releases.
void gt_http_shutdown(void* sv) {
  auto* s = (HttpServer*)sv;
  s->stopping.store(true);
  s->cv.notify_all();
  for (auto& a : s->acceptors) {
    uint64_t one_u = 1;
    (void)!write(a->evfd, &one_u, 8);
  }
  for (auto& a : s->acceptors) a->loop.join();
  std::lock_guard<std::mutex> lk(s->mu);
  for (auto& a : s->acceptors) {
    for (auto& [fd, c] : a->conns) {
      close(fd);
      delete c;
    }
    a->conns.clear();
  }
  http_destroy_acceptors(s);
}

void gt_http_free(void* sv) {
  auto* s = (HttpServer*)sv;
  for (auto& [t, p] : s->inflight) delete p;
  for (auto* p : s->ready) delete p;
  delete s;
}

}  // extern "C"

// ======================================================================
// Native ingress service loop (gt_ingress_*): the GIL-free hot path
// between the socket and the device pipeline.
//
// PR 8 proved the REQUEST half (gt_frame_parse: one GIL-released pass
// from bytes to kernel-ready columns); this closes the LOOP.  The
// steady-state columnar front door — accept -> GUBC kind-5 validate ->
// FNV-1 hash + ring-route (the native twin of
// hash_ring.get_batch_codes) -> enqueue into the ingress ring ->
// kind-6 response fill -> write — now runs entirely in C++ on worker
// threads, with Python touching ONE take/dispatch/complete round per
// BATCH (many coalesced frames), exactly the reference's shape: its
// whole request loop is compiled Go with no interpreter anywhere
// (daemon.go / the gRPC service surface).
//
// Contract with the Python tier:
//   gt_ingress_submit(server, batcher, token) — called by a gateway
//     worker right after gt_http_next handed it a POST whose body
//     magic-sniffs as a kind-5 frame.  GIL released for the whole call
//     (ctypes).  Returns 0 = handled natively (enqueued, or shed with
//     a staged 429); > 0 = fall back to the Python path (malformed
//     frame, trace trailer, slow behavior bits, validation-error
//     lanes, remote-owned lanes, disabled/oversize) — the HttpPending
//     is untouched and Python serves the request exactly as before,
//     which is what keeps every error's wording and the mixed-version
//     interop byte-identical.
//     A classic JSON call of the same endpoint is offered here too
//     (the body's first bytes say which): parsed by gt_json_parse on
//     this thread, it becomes the frame of its checks (call_as_frame)
//     and from there passes the same checks, queues and take; what
//     Python alone answers exactly (JSON the parser refuses, a lane
//     with a validation code, 0 or more than 1000 checks) falls back
//     the same way, counted as call_fallbacks.
//   gt_ingress_take — the Python pump thread blocks here (GIL
//     released) and receives ONE coalesced batch: contiguous
//     kernel-ready column arrays spanning every pending frame (plus
//     the FNV-1 hashes the route already computed, for the hot-key
//     sketch, and name/uk columns for the tenant fold) — zero-copy
//     numpy views, no per-frame Python.
//   gt_ingress_complete — after the device round, one call fans the
//     result arrays back out: per frame, slice -> kind-6 frame encode
//     -> HTTP wrap -> stage on the owning acceptor.  The bytes are
//     identical to wire.encode_ingress_result_frame for the
//     no-override/no-owner case (golden-tested), so a client cannot
//     tell the native loop from the PR 8 path.  A classic call's slice
//     is rendered by gt_json_render instead, the bytes the Python
//     route's render_result_native gives (tests/test_native_calls.py).
//
// Lanes that need Python semantics (a Gregorian duration upstream
// answers with an error, per-lane validation errors, sampled traces,
// remote owners, and a bit of behavior_mask) make the WHOLE frame fall
// back: correctness never depends on the fast lane, it only removes
// interpreter time from the already-columnar common case.  The pump
// sets behavior_mask with the ring, in one call: GLOBAL and
// MULTI_REGION are in it only while the ring has another node (a
// GLOBAL lane may then be a replica's); in an all-self ring their lanes
// are the owner's own, change no answer and stay, and the pump does the
// owner's book-keeping a take at a time.  NO_BATCHING lanes stay native
// with GUBER_EXPRESS on and jump the queue (express_mask / xq below) —
// the bit means "skip coalescing waits", which is satisfiable entirely
// in this loop — and fall back (the PR 13 behavior) when the lane is
// off.
// ======================================================================

namespace {

// Strict UTF-8 validation (RFC 3629: no surrogates, no overlongs, max
// U+10FFFF) — parity with the Python decode edge's .decode("utf-8"),
// which 400s invalid client strings before they can 500 deep in a slow
// lane.
bool utf8_valid(const char* p, size_t len) {
  const unsigned char* s = (const unsigned char*)p;
  const unsigned char* end = s + len;
  while (s < end) {
    unsigned char c = *s;
    if (c < 0x80) { ++s; continue; }
    int extra;
    unsigned int cp;
    if ((c & 0xE0) == 0xC0) { extra = 1; cp = c & 0x1F; }
    else if ((c & 0xF0) == 0xE0) { extra = 2; cp = c & 0x0F; }
    else if ((c & 0xF8) == 0xF0) { extra = 3; cp = c & 0x07; }
    else return false;
    if (s + 1 + extra > end) return false;
    for (int i = 1; i <= extra; ++i) {
      if ((s[i] & 0xC0) != 0x80) return false;
      cp = (cp << 6) | (s[i] & 0x3F);
    }
    if (extra == 1 && cp < 0x80) return false;
    if (extra == 2 && (cp < 0x800 || (cp >= 0xD800 && cp <= 0xDFFF)))
      return false;
    if (extra == 3 && (cp < 0x10000 || cp > 0x10FFFF)) return false;
    s += 1 + extra;
  }
  return true;
}

// Immutable ring snapshot, swapped atomically under the batcher lock
// (set_peers pushes a new one; in-flight submits keep their reference).
struct RingSnap {
  std::vector<uint64_t> vh;     // sorted vnode hashes
  std::vector<uint8_t> vself;   // vnode owner == this daemon
  bool all_self = false;        // every peer is self: skip the search
  int hash_variant = 0;         // 0 = fnv1, 1 = fnv1a (hash_ring)
};

struct IngressFrame {
  HttpServer* srv;
  uint64_t token;
  int acceptor;
  bool keep_alive;
  bool express = false;  // NO_BATCHING lane(s): rides the express queue
  bool call = false;     // a classic JSON call: answered as JSON, not kind-6
  int32_t beh_or = 0;    // OR of the lanes' behaviour words (gt_ingress_submit)
  std::string body;   // the bytes the client sent; a frame's columns view into it
  // A call's columns, parsed out of its JSON into a kind-5 frame's layout
  // (offset and blob of the names, of the unique keys, then the five
  // numeric columns), so that `info` positions them as it does a frame's.
  std::string cols;
  GtFrameInfo info;   // positions against base()
  const char* base() const { return call ? cols.data() : body.data(); }
  int64_t n;
  std::string hk;                 // packed hash keys (name + '_' + uk)
  std::vector<int64_t> hkoff;     // n+1
  std::vector<uint64_t> hashes;   // ring hash per lane
  std::chrono::steady_clock::time_point arrival;
  int64_t parse_ns;
  int64_t t_first_byte = 0, t_body = 0;  // the HttpPending's stamps
};

struct TakenBatch {
  std::vector<IngressFrame*> frames;
  int64_t n = 0;
  std::vector<int32_t> algo, beh;
  std::vector<int64_t> hits, limit, dur;
  std::string hk;
  std::vector<int64_t> hkoff;
  std::vector<uint64_t> hashes;
  std::string name_blob, uk_blob;
  std::vector<int64_t> name_off, uk_off;
  std::vector<int64_t> frame_lanes, frame_age_us;
  // Per frame {token, t_first_byte, t_body, arrival} (mono_ns): the
  // edge's stamps of the request, for edge.recv and edge.handoff.
  std::vector<int64_t> frame_stamps;
  // Per frame {address, length} of IngressFrame::body, the bytes the
  // client sent: the black box copies them (blackbox.tap_taken).
  std::vector<int64_t> frame_body;
  // Per frame: 1 = a classic JSON call, 0 = a kind-5 frame.
  std::vector<uint8_t> frame_call;
  int64_t n_calls = 0;
  int64_t parse_ns_total = 0;
  int32_t beh_or = 0;  // OR of the frames' beh_or: what the take holds
};

struct IngressBatcher {
  std::mutex mu;
  std::condition_variable cv;
  std::deque<IngressFrame*> q;
  // Express queue (the millisecond express lane): frames carrying a
  // NO_BATCHING lane jump here and every take() serves it FIRST, so
  // the lowest-latency request class never waits behind coalesced
  // bulk frames.  Same shed bound, same batch coalescing — only the
  // service order differs.
  std::deque<IngressFrame*> xq;
  int64_t pending_lanes = 0;
  bool stopping = false;
  // config (gt_ingress_set_ring)
  bool enabled = false;
  std::shared_ptr<const RingSnap> ring;
  int64_t cap_lanes = 0;       // shed bound; 0 = unbounded
  int64_t max_frame_lanes = 16384;
  int32_t behavior_mask = 0;   // any set bit -> Python fallback
  int32_t express_mask = 0;    // any set bit -> express queue (0 = off)
  // counters
  int64_t frames = 0, lanes = 0, batches = 0;
  int64_t shed_frames = 0, shed_lanes = 0;
  int64_t fallbacks = 0;
  int64_t express_frames = 0, express_lanes = 0;
  // Classic JSON calls: kept by the lane, and offered but handed back.
  // (`frames` and `fallbacks` count kind-5 frames only; `lanes`,
  // `batches` and the express and shed counts are the lane's, of both.)
  int64_t calls = 0, call_fallbacks = 0;
};

void ingress_free_frame(IngressFrame* f) { delete f; }

}  // namespace

extern "C" {

typedef struct {
  int64_t n, n_frames;
  const int32_t* algo;
  const int32_t* beh;
  const int64_t* hits;
  const int64_t* limit;
  const int64_t* duration;
  const char* hk;
  const int64_t* hkoff;
  int64_t hk_bytes;
  const uint64_t* hashes;
  const char* name_blob;
  const int64_t* name_off;
  int64_t name_bytes;
  const char* uk_blob;
  const int64_t* uk_off;
  int64_t uk_bytes;
  const int64_t* frame_lanes;
  const int64_t* frame_age_us;
  const int64_t* frame_stamps;  // i64[n_frames * 4], see TakenBatch
  int64_t parse_ns_total;
  int64_t hits_total;  // sum of `hits`: the audit's ingress_hits
  const int64_t* frame_body;  // i64[n_frames * 2], see TakenBatch
  int64_t beh_or;  // OR of every lane's behaviour word
  const uint8_t* frame_call;  // u8[n_frames], 1 = a classic JSON call
  int64_t n_calls;
} GtTakenInfo;

void* gt_ingress_new(void) { return new IngressBatcher; }

// Push the route/config snapshot (service.set_peers): sorted vnode
// hashes + per-vnode self bits (the integer-owner-code pass of
// hash_ring.get_batch_codes collapsed to the one question the fast
// lane asks: "is every lane owned here?"), plus the knobs.  enabled=0
// makes every submit fall back (handoff windows, non-default hash_fn,
// GUBER_NATIVE_INGRESS=0).
void gt_ingress_set_ring(void* bv, const uint64_t* vh, const uint8_t* vself,
                         int64_t nv, int32_t all_self, int32_t enabled,
                         int64_t cap_lanes, int64_t max_frame_lanes,
                         int32_t behavior_mask, int32_t hash_variant,
                         int32_t express_mask) {
  auto* b = (IngressBatcher*)bv;
  auto snap = std::make_shared<RingSnap>();
  snap->vh.assign(vh, vh + nv);
  snap->vself.assign(vself, vself + nv);
  snap->all_self = all_self != 0;
  snap->hash_variant = hash_variant;
  std::lock_guard<std::mutex> lk(b->mu);
  b->ring = std::move(snap);
  b->enabled = enabled != 0;
  b->cap_lanes = cap_lanes;
  b->max_frame_lanes = max_frame_lanes;
  b->behavior_mask = behavior_mask;
  b->express_mask = express_mask;
}

constexpr int32_t kBehaviorGregorian = 4;  // Behavior.DURATION_IS_GREGORIAN
// config.MAX_BATCH_SIZE: a classic call of more checks is Python's to
// answer (OutOfRange and its wording).
constexpr int64_t kMaxCallChecks = 1000;

}  // extern "C"

namespace {

// A parsed classic call as the kind-5 frame of the same checks
// (wire.encode_ingress_frame's layout), so that one code path validates,
// hashes, routes and takes it: name and unique-key columns (u32 blob
// length, u32 offsets[n+1], blob), then algorithm, behaviour, hits,
// limit and duration.
std::string call_as_frame(const JsonBatch& jb, const char* body) {
  size_t n = jb.algo.size();
  size_t nbytes = 0, ubytes = 0;
  for (size_t i = 0; i < n; ++i) {
    nbytes += (size_t)jb.nspan[2 * i + 1];
    ubytes += (size_t)jb.ukspan[2 * i + 1];
  }
  std::string out;
  out.reserve(10 + 2 * (4 + 4 * (n + 1)) + nbytes + ubytes + n * 32);
  auto u32 = [&](uint32_t v) { out.append((const char*)&v, 4); };
  out.append("GUBC\x01\x05", 6);
  u32((uint32_t)n);
  auto str_col = [&](const std::vector<int64_t>& span, size_t bytes) {
    u32((uint32_t)bytes);
    uint32_t off = 0;
    u32(off);
    for (size_t i = 0; i < n; ++i) u32(off += (uint32_t)span[2 * i + 1]);
    for (size_t i = 0; i < n; ++i)
      out.append(body + span[2 * i], (size_t)span[2 * i + 1]);
  };
  str_col(jb.nspan, nbytes);
  str_col(jb.ukspan, ubytes);
  out.append((const char*)jb.algo.data(), n * 4);
  out.append((const char*)jb.behavior.data(), n * 4);
  out.append((const char*)jb.hits.data(), n * 8);
  out.append((const char*)jb.limit.data(), n * 8);
  out.append((const char*)jb.duration.data(), n * 8);
  return out;
}

}  // namespace

extern "C" {

// The fast-lane entry (see the banner for the contract).  The body's
// first bytes say what it is: the GUBC magic a kind-5 frame, anything
// else a classic JSON call (HttpEdge.next offers no other).  A call is
// parsed here, on the worker's thread with the interpreter released
// (gt_json_parse), and from there on IS the frame of its checks
// (call_as_frame): it passes the same checks in the same order, rides
// the same queues and the same take, and differs again only in how it
// is answered (gt_ingress_complete) and counted (calls / call_fallbacks).
// What Python alone answers exactly goes back whole: JSON the parser
// refuses (escapes in a name or key, floats, a behaviour list, nested
// values, duplicate `requests`, trailing bytes), a lane with a
// validation code (its error object and wording), no check or more than
// kMaxCallChecks.  Returns 0 = handled natively; >0 = Python fallback
// reason (1 malformed/bad-utf8, 2 trace trailer, 3 empty/oversize, 4
// slow behavior bits, 5 validation-error lanes, 6 disabled, 7
// remote-owned lanes); -1 = unknown token.
int gt_ingress_submit(void* sv, void* bv, uint64_t token) {
  auto* s = (HttpServer*)sv;
  auto* b = (IngressBatcher*)bv;
  HttpPending* p;
  {
    std::lock_guard<std::mutex> lk(s->mu);
    auto it = s->inflight.find(token);
    if (it == s->inflight.end()) return -1;
    p = it->second;
  }
  bool enabled;
  std::shared_ptr<const RingSnap> ring;
  int64_t max_frame_lanes;
  int32_t behavior_mask;
  int32_t express_mask;
  {
    std::lock_guard<std::mutex> lk(b->mu);
    enabled = b->enabled && !b->stopping;
    ring = b->ring;
    max_frame_lanes = b->max_frame_lanes;
    behavior_mask = b->behavior_mask;
    express_mask = b->express_mask;
  }
  const bool call =
      p->body.size() < 4 || memcmp(p->body.data(), "GUBC", 4) != 0;
  auto bump_fallback = [&](int code) {
    std::lock_guard<std::mutex> lk(b->mu);
    ++(call ? b->call_fallbacks : b->fallbacks);
    return code;
  };
  if (!enabled || !ring) return bump_fallback(6);
  auto t0 = std::chrono::steady_clock::now();
  auto frame = std::make_unique<IngressFrame>();
  const std::string* src = &p->body;
  if (call) {
    std::unique_ptr<JsonBatch> jb((JsonBatch*)gt_json_parse(
        p->body.data(), (int64_t)p->body.size()));
    if (!jb) return bump_fallback(1);  // Python's json.loads and its 400
    int64_t checks = (int64_t)jb->algo.size();
    if (checks == 0 || checks > kMaxCallChecks) return bump_fallback(3);
    for (uint8_t e : jb->err)
      if (e) return bump_fallback(5);  // empty field, bad enum: Python's words
    // Each JSON string on its own (the frame check below reads a column's
    // blob whole, where one string's torn tail could borrow the next's head).
    const char* raw = p->body.data();
    for (int64_t i = 0; i < checks; ++i)
      if (!utf8_valid(raw + jb->nspan[2 * i], (size_t)jb->nspan[2 * i + 1]) ||
          !utf8_valid(raw + jb->ukspan[2 * i], (size_t)jb->ukspan[2 * i + 1]))
        return bump_fallback(1);
    frame->cols = call_as_frame(*jb, raw);
    src = &frame->cols;
  }
  GtFrameInfo info;
  void* h = gt_frame_parse(src->data(), (int64_t)src->size(), 5, &info);
  if (!h) return bump_fallback(1);  // Python owns the 400 wording
  gt_frame_free(h);                 // positions captured in `info`
  if (info.trace_count > 0) return bump_fallback(2);  // sampled: span links
  int64_t n = info.n;
  if (n == 0 || n > max_frame_lanes) return bump_fallback(3);
  const char* body = src->data();
  // A bit of behavior_mask needs the Python router's semantics: GLOBAL
  // and MULTI_REGION while the ring has another node (the pump clears
  // them from the mask of an all-self ring, whose lanes are all the
  // owner's), NO_BATCHING when the express lane is off.  With the
  // express lane on, NO_BATCHING lanes instead flag the frame for the
  // express queue below.  A DURATION_IS_GREGORIAN lane stays here (the
  // pump resolves its interval) unless its duration is not one upstream
  // resolves, weeks (3) or anything outside 0-5: the Python path owns
  // that lane's error wording (utils/gregorian.py).
  bool xpress = false;
  int32_t beh_or = 0;
  for (int64_t i = 0; i < n; ++i) {
    int32_t bh;
    memcpy(&bh, body + info.beh_pos + 4 * i, 4);
    beh_or |= bh;
    if (bh & behavior_mask) return bump_fallback(4);
    if (bh & kBehaviorGregorian) {
      int64_t d;
      memcpy(&d, body + info.dur_pos + 8 * i, 8);
      if (d < 0 || d > 5 || d == 3) return bump_fallback(4);
    }
    if (bh & express_mask) xpress = true;
  }
  // Build the packed hash keys + validation codes (the gt_frame_fill
  // pass, inlined so an error lane can bail early), then the UTF-8
  // parity check the Python decode edge makes.
  frame->hk.reserve((size_t)info.hk_bytes);
  frame->hkoff.resize((size_t)n + 1);
  const char* noff = body + info.name_off_pos;
  const char* uoff = body + info.uk_off_pos;
  const char* nblob = body + info.name_blob_pos;
  const char* ublob = body + info.uk_blob_pos;
  for (int64_t i = 0; i < n; ++i) {
    frame->hkoff[(size_t)i] = (int64_t)frame->hk.size();
    uint32_t n0 = frame_u32(noff + 4 * i), n1 = frame_u32(noff + 4 * (i + 1));
    uint32_t u0 = frame_u32(uoff + 4 * i), u1 = frame_u32(uoff + 4 * (i + 1));
    if (u1 == u0 || n1 == n0) return bump_fallback(5);  // validation lanes
    frame->hk.append(nblob + n0, n1 - n0);
    frame->hk.push_back('_');
    frame->hk.append(ublob + u0, u1 - u0);
  }
  frame->hkoff[(size_t)n] = (int64_t)frame->hk.size();
  {
    uint32_t ntot = frame_u32(noff + 4 * n), utot = frame_u32(uoff + 4 * n);
    if (!utf8_valid(nblob, ntot) || !utf8_valid(ublob, utot))
      return bump_fallback(1);
  }
  // FNV-1 hash + ring-route: the native ownership-code pass.  Any lane
  // owned elsewhere -> the Python router (it groups/forwards).
  frame->hashes.resize((size_t)n);
  for (int64_t i = 0; i < n; ++i) {
    const char* kp = frame->hk.data() + frame->hkoff[(size_t)i];
    const char* ke = frame->hk.data() + frame->hkoff[(size_t)i + 1];
    frame->hashes[(size_t)i] =
        ring->hash_variant ? fnv1a64(kp, ke) : fnv1_64(kp, ke);
  }
  if (!ring->all_self) {
    const auto& vh = ring->vh;
    if (vh.empty()) return bump_fallback(7);
    for (int64_t i = 0; i < n; ++i) {
      size_t idx = (size_t)(std::lower_bound(vh.begin(), vh.end(),
                                             frame->hashes[(size_t)i]) -
                            vh.begin());
      if (idx == vh.size()) idx = 0;
      if (!ring->vself[idx]) return bump_fallback(7);
    }
  }
  frame->srv = s;
  frame->token = token;
  frame->acceptor = p->acceptor;
  frame->keep_alive = p->keep_alive;
  frame->n = n;
  frame->call = call;
  frame->beh_or = beh_or;
  frame->info = info;
  frame->arrival = t0;
  frame->t_first_byte = p->t_first_byte;
  frame->t_body = p->t_body;
  frame->parse_ns = (int64_t)std::chrono::duration_cast<std::chrono::nanoseconds>(
                        std::chrono::steady_clock::now() - t0)
                        .count();
  // Shed bound + enqueue decided under ONE batcher lock: a submit
  // losing the race with gt_ingress_stop must NOT push a frame after
  // stop drained the queue — no pump would remain to answer it and
  // the client would hang to its own deadline.  The stopping verdict
  // here keeps the HttpPending intact, so the request falls back to
  // the Python path (which owns the shutdown 503).
  int64_t queued = 0, cap = 0;
  int verdict;  // 0 = enqueued, 1 = shed, 2 = stopping/disabled
  {
    std::lock_guard<std::mutex> lk(b->mu);
    if (b->stopping || !b->enabled) {
      verdict = 2;
    } else {
      queued = b->pending_lanes;
      cap = b->cap_lanes;
      if (cap > 0 && queued + n > cap) {
        verdict = 1;
        ++b->shed_frames;
        b->shed_lanes += n;
      } else {
        verdict = 0;
        b->pending_lanes += n;
        ++(call ? b->calls : b->frames);
        b->lanes += n;
        // A frame's columns keep viewing the moved body; ownership
        // transfers to the queue inside the lock so no stop() can slip
        // between.
        frame->body = std::move(p->body);
        frame->express = xpress;
        if (xpress) {
          ++b->express_frames;
          b->express_lanes += n;
          b->xq.push_back(frame.release());
        } else {
          b->q.push_back(frame.release());
        }
      }
    }
  }
  if (verdict == 2) return bump_fallback(6);
  if (verdict == 1) {
    // Answer the 429 natively, byte-identical to the Python
    // IngressShedError triplet, without queueing work the device
    // cannot serve inside any useful deadline.
    std::string msg =
        "{\"code\": 2, \"message\": \"ingress queue saturated (" +
        std::to_string(queued) + " lanes queued, cap " +
        std::to_string(cap) + "); retry with backoff\"}";
    std::string resp =
        http_envelope(429, "Error", "application/json", msg.data(),
                      (int64_t)msg.size());
    {
      std::lock_guard<std::mutex> lk(s->mu);
      s->inflight.erase(token);
    }
    delete p;
    http_stage_response(s, token, std::move(resp));
    return 0;
  }
  {
    std::lock_guard<std::mutex> lk(s->mu);
    s->inflight.erase(token);
    if (!call && (size_t)p->acceptor < s->acceptors.size()) {
      HttpAcceptor* a = s->acceptors[(size_t)p->acceptor].get();
      ++a->ingress_frames;
      a->ingress_lanes += n;
    }
  }
  delete p;
  b->cv.notify_one();
  return 0;
}

// Python pump: block (GIL released) for one coalesced batch of up to
// max_lanes lanes (the first frame always fits — frames are capped at
// max_frame_lanes <= any sane take bound).  1 = *out filled, handle in
// *out_tb (pointers valid until gt_ingress_complete/fail); 0 =
// timeout; -1 = stopping and drained.
int gt_ingress_take(void* bv, int64_t max_lanes, int64_t timeout_ms,
                    void** out_tb, GtTakenInfo* out) {
  auto* b = (IngressBatcher*)bv;
  auto tb = std::make_unique<TakenBatch>();
  {
    std::unique_lock<std::mutex> lk(b->mu);
    if (!b->cv.wait_for(lk, std::chrono::milliseconds(timeout_ms), [&] {
          return !b->q.empty() || !b->xq.empty() || b->stopping;
        })) {
      return 0;
    }
    if (b->q.empty() && b->xq.empty()) return -1;  // stopping
    // Express frames first AND pure (the lane's whole point: a
    // NO_BATCHING frame never waits behind coalesced bulk backlog —
    // an express take must not keep filling from the bulk queue, or
    // the express response would wait out a full up-to-max_lanes
    // dispatch).  Express frames
    // coalesce among THEMSELVES (window-free coalescing); bulk frames
    // ride the next take — with multiple pump threads, usually a
    // concurrent one.  NO_BATCHING callers opting out of batching pay
    // their own dispatch, the reference's semantics.
    bool express_take = !b->xq.empty();
    std::deque<IngressFrame*>& src = express_take ? b->xq : b->q;
    while (!src.empty()) {
      IngressFrame* f = src.front();
      if (!tb->frames.empty() && tb->n + f->n > max_lanes) break;
      src.pop_front();
      b->pending_lanes -= f->n;
      tb->n += f->n;
      tb->frames.push_back(f);
    }
    ++b->batches;
  }
  int64_t n = tb->n;
  tb->algo.resize((size_t)n);
  tb->beh.resize((size_t)n);
  tb->hits.resize((size_t)n);
  tb->limit.resize((size_t)n);
  tb->dur.resize((size_t)n);
  tb->hkoff.resize((size_t)n + 1);
  tb->name_off.resize((size_t)n + 1);
  tb->uk_off.resize((size_t)n + 1);
  tb->hashes.resize((size_t)n);
  tb->frame_lanes.resize(tb->frames.size());
  tb->frame_age_us.resize(tb->frames.size());
  tb->frame_stamps.resize(tb->frames.size() * 4);
  tb->frame_body.resize(tb->frames.size() * 2);
  tb->frame_call.resize(tb->frames.size());
  auto now = std::chrono::steady_clock::now();
  int64_t lo = 0;
  tb->hkoff[0] = tb->name_off[0] = tb->uk_off[0] = 0;
  for (size_t fi = 0; fi < tb->frames.size(); ++fi) {
    IngressFrame* f = tb->frames[fi];
    int64_t m = f->n;
    const char* body = f->base();
    memcpy(tb->algo.data() + lo, body + f->info.algo_pos, (size_t)m * 4);
    memcpy(tb->beh.data() + lo, body + f->info.beh_pos, (size_t)m * 4);
    memcpy(tb->hits.data() + lo, body + f->info.hits_pos, (size_t)m * 8);
    memcpy(tb->limit.data() + lo, body + f->info.limit_pos, (size_t)m * 8);
    memcpy(tb->dur.data() + lo, body + f->info.dur_pos, (size_t)m * 8);
    memcpy(tb->hashes.data() + lo, f->hashes.data(), (size_t)m * 8);
    int64_t hk_base = (int64_t)tb->hk.size();
    tb->hk += f->hk;
    for (int64_t i = 0; i < m; ++i)
      tb->hkoff[(size_t)(lo + i) + 1] = hk_base + f->hkoff[(size_t)i + 1];
    const char* noff = body + f->info.name_off_pos;
    const char* uoff = body + f->info.uk_off_pos;
    int64_t nb_base = (int64_t)tb->name_blob.size();
    int64_t ub_base = (int64_t)tb->uk_blob.size();
    tb->name_blob.append(body + f->info.name_blob_pos, frame_u32(noff + 4 * m));
    tb->uk_blob.append(body + f->info.uk_blob_pos, frame_u32(uoff + 4 * m));
    for (int64_t i = 0; i < m; ++i) {
      tb->name_off[(size_t)(lo + i) + 1] =
          nb_base + (int64_t)frame_u32(noff + 4 * (i + 1));
      tb->uk_off[(size_t)(lo + i) + 1] =
          ub_base + (int64_t)frame_u32(uoff + 4 * (i + 1));
    }
    tb->frame_lanes[fi] = m;
    tb->frame_age_us[fi] =
        (int64_t)std::chrono::duration_cast<std::chrono::microseconds>(
            now - f->arrival)
            .count();
    tb->frame_stamps[fi * 4 + 0] = (int64_t)f->token;
    tb->frame_stamps[fi * 4 + 1] = f->t_first_byte;
    tb->frame_stamps[fi * 4 + 2] = f->t_body;
    tb->frame_stamps[fi * 4 + 3] = ns_of(f->arrival);
    tb->frame_body[fi * 2 + 0] = (int64_t)(intptr_t)f->body.data();
    tb->frame_body[fi * 2 + 1] = (int64_t)f->body.size();
    tb->frame_call[fi] = f->call;
    tb->n_calls += f->call;
    tb->parse_ns_total += f->parse_ns;
    tb->beh_or |= f->beh_or;
    lo += m;
  }
  out->n = n;
  out->n_frames = (int64_t)tb->frames.size();
  out->algo = tb->algo.data();
  out->beh = tb->beh.data();
  out->hits = tb->hits.data();
  out->limit = tb->limit.data();
  out->duration = tb->dur.data();
  out->hk = tb->hk.data();
  out->hkoff = tb->hkoff.data();
  out->hk_bytes = (int64_t)tb->hk.size();
  out->hashes = tb->hashes.data();
  out->name_blob = tb->name_blob.data();
  out->name_off = tb->name_off.data();
  out->name_bytes = (int64_t)tb->name_blob.size();
  out->uk_blob = tb->uk_blob.data();
  out->uk_off = tb->uk_off.data();
  out->uk_bytes = (int64_t)tb->uk_blob.size();
  out->frame_lanes = tb->frame_lanes.data();
  out->frame_age_us = tb->frame_age_us.data();
  out->frame_stamps = tb->frame_stamps.data();
  out->parse_ns_total = tb->parse_ns_total;
  out->hits_total = 0;
  for (int64_t h : tb->hits) out->hits_total += h;
  out->frame_body = tb->frame_body.data();
  out->beh_or = tb->beh_or;
  out->frame_call = tb->frame_call.data();
  out->n_calls = tb->n_calls;
  *out_tb = tb.release();
  return 1;
}

// Response fill: slice the result arrays per frame and answer each in
// the encoding it came in.  A kind-5 frame: the kind-6 frame
// (byte-identical to wire.encode_ingress_result_frame with no overrides
// and no owner columns — the fast lane's invariant).  A classic call:
// the JSON body gt_json_render gives its slice with no overrides, what
// gateway.render_result_native renders on the Python route.  Wrapped in
// the HTTP envelope gt_http_respond emits and staged on the owning
// acceptor.  One call per batch; releases the handle.
void gt_ingress_complete(void* tbv, const int32_t* status,
                         const int64_t* limit, const int64_t* remaining,
                         const int64_t* reset) {
  auto* tb = (TakenBatch*)tbv;
  int64_t lo = 0;
  std::string frame;
  for (IngressFrame* f : tb->frames) {
    int64_t m = f->n;
    frame.clear();
    const char* ctype = "application/x-gubernator-columns";
    if (f->call) {
      ctype = "application/json";
      frame.resize((size_t)m * 160 + 32);  // gt_json_render's worst case
      int64_t len = gt_json_render(status + lo, limit + lo, remaining + lo,
                                   reset + lo, m, nullptr, 0, nullptr, nullptr,
                                   &frame[0], (int64_t)frame.size());
      frame.resize((size_t)(len < 0 ? 0 : len));
    } else {
      frame.reserve(10 + (size_t)m * (4 + 8 + 8 + 8) + 8);
      frame.append("GUBC", 4);
      uint8_t vk[2] = {1, 6};
      frame.append((const char*)vk, 2);
      uint32_t m32 = (uint32_t)m;
      frame.append((const char*)&m32, 4);
      frame.append((const char*)(status + lo), (size_t)m * 4);
      frame.append((const char*)(limit + lo), (size_t)m * 8);
      frame.append((const char*)(remaining + lo), (size_t)m * 8);
      frame.append((const char*)(reset + lo), (size_t)m * 8);
      uint32_t zero = 0;
      frame.append((const char*)&zero, 4);  // n_owner_addrs = 0
      frame.append((const char*)&zero, 4);  // n_overrides = 0
    }
    std::string resp = http_envelope(200, "OK", ctype, frame.data(),
                                     (int64_t)frame.size());
    http_stage_response(f->srv, f->token, std::move(resp));
    lo += m;
    ingress_free_frame(f);
  }
  tb->frames.clear();
  delete tb;
}

// Error fill (dispatch failure): every frame of the batch answers the
// same triplet the Python error path would emit.  Releases the handle.
void gt_ingress_fail(void* tbv, int status, const char* reason,
                     const char* ctype, const char* body, int64_t blen) {
  auto* tb = (TakenBatch*)tbv;
  std::string resp = http_envelope(status, reason && *reason ? reason : "Error",
                                   ctype, body, blen);
  for (IngressFrame* f : tb->frames) {
    http_stage_response(f->srv, f->token, std::string(resp));
    ingress_free_frame(f);
  }
  tb->frames.clear();
  delete tb;
}

// Stop: wake the pump (take returns -1 once drained) and answer every
// still-queued frame 503, the worker loop's shutdown wording.
void gt_ingress_stop(void* bv) {
  auto* b = (IngressBatcher*)bv;
  std::deque<IngressFrame*> q;
  {
    std::lock_guard<std::mutex> lk(b->mu);
    b->stopping = true;
    b->enabled = false;
    q.swap(b->q);
    for (IngressFrame* f : b->xq) q.push_back(f);
    b->xq.clear();
    b->pending_lanes = 0;
  }
  b->cv.notify_all();
  const char* msg = "{\"code\": 14, \"message\": \"shutting down\"}";
  std::string resp = http_envelope(503, "Error", "application/json", msg,
                                   (int64_t)strlen(msg));
  for (IngressFrame* f : q) {
    http_stage_response(f->srv, f->token, std::string(resp));
    ingress_free_frame(f);
  }
}

// out: i64[12] = {frames, lanes, batches, shed_frames, shed_lanes,
// fallbacks, pending_frames, pending_lanes, express_frames,
// express_lanes, calls, call_fallbacks}.  Cumulative; the Python scrape
// keeps last-seen values and feeds deltas into the prometheus counters.
void gt_ingress_stats(void* bv, int64_t* out) {
  auto* b = (IngressBatcher*)bv;
  std::lock_guard<std::mutex> lk(b->mu);
  out[0] = b->frames;
  out[1] = b->lanes;
  out[2] = b->batches;
  out[3] = b->shed_frames;
  out[4] = b->shed_lanes;
  out[5] = b->fallbacks;
  out[6] = (int64_t)(b->q.size() + b->xq.size());
  out[7] = b->pending_lanes;
  out[8] = b->express_frames;
  out[9] = b->express_lanes;
  out[10] = b->calls;
  out[11] = b->call_fallbacks;
}

void gt_ingress_free(void* bv) {
  auto* b = (IngressBatcher*)bv;
  for (IngressFrame* f : b->q) ingress_free_frame(f);
  for (IngressFrame* f : b->xq) ingress_free_frame(f);
  delete b;
}

}  // extern "C"

// ======================================================================
// Batch folds of the observability planes (gt_name_groups,
// gt_cms_fold): the tenant ledger's per-name aggregation and the
// count-min adds of both sketches
// (profiling.TenantLedger, saturation.HotKeySketch), one pass each over
// columns the caller already holds.  The Python objects stay the only
// holders of their state: a fold adds into the numpy table's own
// buffer, under the object's own lock, and hands back what the top-K
// bookkeeping needs — at most `topk` candidates — so the interpreter
// never walks a batch's lanes.
// ======================================================================

namespace {

// First-occurrence group-by of 64-bit hashes: an open-addressing set of
// positions into the caller's array of distinct hashes.  The slots are
// a thread's scratch, kept between folds: a take's worth is 32 KB, and
// a fresh block that size a fold is page faults, not arithmetic.
struct HashGroups {
  std::vector<uint32_t>& slots;  // position + 1; 0 = empty
  const uint64_t* keys;          // the distinct hashes, by position
  uint64_t mask;
  int shift;
  static std::vector<uint32_t>& scratch() {
    static thread_local std::vector<uint32_t> v;
    return v;
  }
  HashGroups(int64_t n, const uint64_t* distinct)
      : slots(scratch()), keys(distinct) {
    int bits = 4;
    while ((int64_t(1) << bits) < 2 * n) ++bits;
    size_t size = size_t(1) << bits;
    if (slots.size() < size) slots.resize(size);
    memset(slots.data(), 0, size * sizeof(uint32_t));
    mask = size - 1;
    shift = 64 - bits;
  }
  // The slot `h` lives in, or the empty one it would take.
  uint32_t& find(uint64_t h) {
    uint64_t i = (h * 0x9E3779B97F4A7C15ull) >> shift;
    while (slots[i] != 0 && keys[slots[i] - 1] != h) i = (i + 1) & mask;
    return slots[i];
  }
};

// memcmp(p, q, len) == 0 for the short strings names are, without the
// call: eight bytes a step, then the tail.
inline bool same_bytes(const char* p, const char* q, size_t len) {
  for (; len >= 8; p += 8, q += 8, len -= 8) {
    uint64_t a, b;
    memcpy(&a, p, 8);
    memcpy(&b, q, 8);
    if (a != b) return false;
  }
  for (; len; ++p, ++q, --len)
    if (*p != *q) return false;
  return true;
}

}  // namespace

extern "C" {

// The tenant fold's aggregation (TenantLedger._fold_batch): lanes,
// hits and ingress bytes by FNV-1 name hash, as np.unique(hashes,
// return_index, return_inverse) plus three bincounts leave them.
// out = i64[6][n]: rows {distinct hashes ASCENDING (u64 bits), first
// lane, lanes, hits, bytes}, each filled to m, then inv = each lane's
// position among the distinct.  A lane's bytes are name_len + uk_len +
// lane_const.  Returns m.
int64_t gt_name_groups(const char* names, const int64_t* name_off, int64_t n,
                       const int64_t* hits, const int64_t* name_len,
                       const int64_t* uk_len, int64_t lane_const,
                       int64_t* out) {
  if (n <= 0) return 0;
  uint64_t* uh = (uint64_t*)out;
  int64_t* inv = out + 5 * n;
  HashGroups groups(n, uh);
  int64_t m = 0;
  const char* prev = nullptr;
  size_t prev_len = 0;
  int64_t prev_g = 0;
  for (int64_t i = 0; i < n; ++i) {
    const char* p = names + name_off[i];
    size_t len = (size_t)(name_off[i + 1] - name_off[i]);
    int64_t g;
    if (prev && len == prev_len && same_bytes(p, prev, len)) {
      g = prev_g;  // a frame is mostly one name: no hash, no probe
    } else {
      uint64_t h = fnv1_64(p, p + len);
      uint32_t& s = groups.find(h);
      if (s == 0) {
        uh[m] = h;
        out[n + m] = i;
        out[2 * n + m] = out[3 * n + m] = out[4 * n + m] = 0;
        s = (uint32_t)++m;
      }
      g = (int64_t)s - 1;
      prev = p;
      prev_len = len;
      prev_g = g;
    }
    inv[i] = g;
    out[2 * n + g] += 1;
    out[3 * n + g] += hits[i];
    out[4 * n + g] += name_len[i] + uk_len[i] + lane_const;
  }
  if (m == 1) return 1;
  // np.unique's order: ascending hash.
  std::vector<int64_t> order((size_t)m), rank((size_t)m);
  for (int64_t g = 0; g < m; ++g) order[(size_t)g] = g;
  std::sort(order.begin(), order.end(),
            [&](int64_t a, int64_t b) { return uh[a] < uh[b]; });
  std::vector<int64_t> sorted((size_t)(5 * m));
  for (int64_t k = 0; k < m; ++k) {
    int64_t g = order[(size_t)k];
    rank[(size_t)g] = k;
    for (int r = 0; r < 5; ++r) sorted[(size_t)(r * m + k)] = out[r * n + g];
  }
  for (int r = 0; r < 5; ++r)
    memcpy(out + r * n, sorted.data() + r * m, (size_t)m * 8);
  for (int64_t i = 0; i < n; ++i) inv[i] = rank[(size_t)inv[i]];
  return m;
}

// One batch into a count-min table and out again as top-K candidates.
// tab = i64[depth][width], the caller's own (its lock held): every
// distinct hash adds its summed weight (weights NULL = 1 a lane) into
// the `depth` cells ((h * salt) >> 17) % width, then reads its estimate
// back, the least of its cells after ALL the adds.  out = i64[3 * n +
// n_tracked + 1 + topk]: per distinct hash in order of first
// occurrence, filled to the returned m, rows of n {the hash (u64
// bits), its first lane, its estimate}; then where each of the caller's
// tracked hashes landed in that order, -1 if not in the batch; then the
// count of candidates and their positions — the untracked with an
// estimate ABOVE `floor`, ascending in position; more than `topk` of
// them are cut to the topk largest estimates, ascending by estimate.
int64_t gt_cms_fold(int64_t* tab, int32_t depth, int64_t width,
                    const uint64_t* salts, const uint64_t* hashes,
                    const int64_t* weights, int64_t n,
                    const uint64_t* tracked, int64_t n_tracked,
                    int64_t floor, int64_t topk, int64_t* out) {
  if (n < 0) n = 0;
  if (topk < 0) topk = 0;
  uint64_t* ud = (uint64_t*)out;
  int64_t* ufirst = out + n;
  int64_t* uest = out + 2 * n;  // a hash's weight until its estimate is read
  int64_t* t_idx = out + 3 * n;
  int64_t* n_cand = t_idx + n_tracked;
  int64_t* cand = n_cand + 1;
  *n_cand = 0;
  for (int64_t t = 0; t < n_tracked; ++t) t_idx[t] = -1;
  if (n == 0 || depth <= 0 || width <= 0) return 0;
  HashGroups groups(n, ud);
  int64_t m = 0;
  for (int64_t i = 0; i < n; ++i) {
    uint32_t& s = groups.find(hashes[i]);
    int64_t w = weights ? weights[i] : 1;
    if (s == 0) {
      ud[m] = hashes[i];
      ufirst[m] = i;
      uest[m] = w;
      s = (uint32_t)++m;
    } else {
      uest[s - 1] += w;
    }
  }
  const bool pow2 = (width & (width - 1)) == 0;
  const uint64_t wmask = (uint64_t)width - 1;
  auto cell = [&](int64_t j, int32_t r) -> int64_t& {
    uint64_t x = (ud[j] * salts[r]) >> 17;
    return tab[(int64_t)r * width + (pow2 ? x & wmask : x % (uint64_t)width)];
  };
  for (int64_t j = 0; j < m; ++j)
    for (int32_t r = 0; r < depth; ++r) cell(j, r) += uest[j];
  for (int64_t j = 0; j < m; ++j) {
    int64_t est = cell(j, 0);
    for (int32_t r = 1; r < depth; ++r) est = std::min(est, cell(j, r));
    uest[j] = est;
  }
  std::vector<uint8_t> is_tracked((size_t)m, 0);
  for (int64_t t = 0; t < n_tracked; ++t) {
    uint32_t s = groups.find(tracked[t]);
    if (s != 0) {
      t_idx[t] = (int64_t)s - 1;
      is_tracked[s - 1] = 1;
    }
  }
  std::vector<int64_t> q;
  for (int64_t j = 0; j < m; ++j)
    if (!is_tracked[(size_t)j] && uest[j] > floor) q.push_back(j);
  if ((int64_t)q.size() > topk) {
    auto weaker = [&](int64_t a, int64_t b) {
      return uest[a] != uest[b] ? uest[a] < uest[b] : a < b;
    };
    std::nth_element(q.begin(), q.end() - topk, q.end(), weaker);
    q.erase(q.begin(), q.end() - topk);
    std::sort(q.begin(), q.end(), weaker);
  }
  *n_cand = (int64_t)q.size();
  if (!q.empty()) memcpy(cand, q.data(), q.size() * 8);
  return m;
}

}  // extern "C"
