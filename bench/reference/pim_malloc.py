"""Plain-Python PIM-malloc: the reference every cell's answers are held to.

A per-thread LIFO size-class freelist frontend over a leftmost-descent
array buddy (PIM-malloc, arXiv:2505.13002, Sec. 3). One protocol round
serves one op per thread in two phases: a batched malloc (new blocks and
relocating reallocs; freelist hits first, then backend work serialised in
thread order), then a batched free. The buddy-metadata cache of the HW/SW
design and the software buffer of the SW design change only what a round
costs, not where blocks go, so both configurations share this reference.

It imports nothing of the program under test: it is a copy of the
repository's Python oracle kept here so that a change to the program
cannot move the yardstick.
"""
from __future__ import annotations

OP_MALLOC, OP_FREE, OP_REALLOC, OP_CALLOC = 1, 2, 3, 4


def _next_pow2(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length() if x > 1 else 1


class Buddy:
    """Array buddy over ``heap_bytes`` with ``min_block`` leaves."""

    def __init__(self, heap_bytes: int, min_block: int):
        if heap_bytes & (heap_bytes - 1) or min_block & (min_block - 1):
            raise ValueError("heap and block sizes must be powers of two")
        self.heap = heap_bytes
        self.min_block = min_block
        n_leaf = heap_bytes // min_block
        self.longest = [0] * (2 * n_leaf)
        for i in range(1, 2 * n_leaf):
            self.longest[i] = heap_bytes >> (i.bit_length() - 1)

    def alloc(self, size: int) -> int:
        size = max(_next_pow2(size), self.min_block)
        if size > self.heap or self.longest[1] < size:
            return -1
        node, node_size = 1, self.heap
        while node_size > size:
            left = 2 * node
            node = left if self.longest[left] >= size else left + 1
            node_size >>= 1
        offset = node * node_size - self.heap
        self.longest[node] = 0
        while node > 1:
            node >>= 1
            self.longest[node] = max(self.longest[2 * node],
                                     self.longest[2 * node + 1])
        return offset

    def free(self, offset: int, size: int) -> bool:
        size = max(_next_pow2(size), self.min_block)
        node = (offset + self.heap) // size
        if offset < 0 or offset >= self.heap or self.longest[node] != 0:
            return False
        self.longest[node] = size
        node_size = size
        while node > 1:
            node >>= 1
            node_size <<= 1
            left, right = self.longest[2 * node], self.longest[2 * node + 1]
            if left == node_size >> 1 and right == node_size >> 1:
                self.longest[node] = node_size
            else:
                self.longest[node] = max(left, right)
        return True


class PimMalloc:
    """One core's heap: ``num_threads`` freelist frontends over one buddy."""

    def __init__(self, heap_bytes: int, num_threads: int, size_classes,
                 block_bytes: int, cap: int):
        self.T = num_threads
        self.classes = list(size_classes)
        self.block = block_bytes
        self.cap = cap
        self.heap = heap_bytes
        self.buddy = Buddy(heap_bytes, block_bytes)
        self.nc = len(self.classes)
        self.counts = [[0] * self.nc for _ in range(num_threads)]
        self.stacks = [[[] for _ in range(self.nc)] for _ in range(num_threads)]
        self.block_cls = {}
        self.block_free = {}
        self.big_log2 = {}
        # every thread starts with one carved block per class
        for t in range(num_threads):
            for c in range(self.nc):
                off = self.buddy.alloc(block_bytes)
                if off < 0:
                    continue
                csize = self.classes[c]
                sub = block_bytes // csize
                self.stacks[t][c] = [off + i * csize for i in range(sub)]
                self.counts[t][c] = sub
                b = off // block_bytes
                self.block_cls[b] = c
                self.block_free[b] = sub

    def copy(self) -> "PimMalloc":
        """An independent heap in the same state (faster than building
        one: a fresh heap carves every thread's blocks again)."""
        new = object.__new__(PimMalloc)
        new.__dict__.update(self.__dict__)
        new.buddy = object.__new__(Buddy)
        new.buddy.__dict__.update(self.buddy.__dict__)
        new.buddy.longest = list(self.buddy.longest)
        new.counts = [list(row) for row in self.counts]
        new.stacks = [[list(s) for s in row] for row in self.stacks]
        new.block_cls = dict(self.block_cls)
        new.block_free = dict(self.block_free)
        new.big_log2 = dict(self.big_log2)
        return new

    def _class_of(self, size: int) -> int:
        for c, s in enumerate(self.classes):
            if size <= s:
                return c
        return self.nc - 1

    def malloc(self, sizes, active):
        T, block, classes = self.T, self.block, self.classes
        ptrs = [-1] * T
        paths = [-1] * T
        backend = []
        for t in range(T):                   # phase A: freelist hits
            if not active[t] or sizes[t] <= 0:
                continue
            size = sizes[t]
            if size <= classes[-1]:
                c = self._class_of(size)
                if self.counts[t][c] > 0:
                    ptr = self.stacks[t][c].pop()
                    self.counts[t][c] -= 1
                    self.block_free[ptr // block] -= 1
                    ptrs[t] = ptr
                    paths[t] = 0
                else:
                    backend.append((t, True, c, size))
            else:
                backend.append((t, False, None, size))
        for t, refill, c, size in backend:   # phase B: in thread order
            if refill:
                off = self.buddy.alloc(block)
                if off < 0:
                    paths[t] = 3
                    continue
                csize = classes[c]
                sub = block // csize
                self.stacks[t][c] = [off + i * csize for i in range(sub - 1)]
                self.counts[t][c] = sub - 1
                b = off // block
                self.block_cls[b] = c
                self.block_free[b] = sub - 1
                ptrs[t] = off + (sub - 1) * csize
                paths[t] = 1
            else:
                asize = max(_next_pow2(size), block)
                off = self.buddy.alloc(asize)
                if off < 0:
                    paths[t] = 3
                    continue
                self.big_log2[off // block] = asize.bit_length() - 1
                ptrs[t] = off
                paths[t] = 2
        return ptrs, paths

    def free(self, ptrs, active):
        """Paths: 0 pushed / 1 big / 2 dropped / -1 idle (NULL is benign)."""
        T, block = self.T, self.block
        paths = [-1] * T
        for t in range(T):
            ptr = ptrs[t]
            if not active[t] or ptr == -1:
                continue
            if ptr < 0 or ptr >= self.heap:
                paths[t] = 2
                continue
            b = ptr // block
            c = self.block_cls.get(b, -1)
            if c >= 0:
                if self.counts[t][c] >= self.cap:
                    paths[t] = 2
                    continue
                self.stacks[t][c].append(ptr)
                self.counts[t][c] += 1
                self.block_free[b] = self.block_free.get(b, 0) + 1
                paths[t] = 0
            elif self.big_log2.get(b, -1) >= 0 and ptr % block == 0:
                self.buddy.free(ptr, 1 << self.big_log2[b])
                del self.big_log2[b]
                paths[t] = 1
            else:
                paths[t] = 2
        return paths

    def _realloc_meta(self, ptr: int, size: int):
        """(valid_old, in_place) for realloc(ptr, size)."""
        block, classes = self.block, self.classes
        valid = 0 <= ptr < self.heap
        b = ptr // block if valid else 0
        cls = self.block_cls.get(b, -1) if valid else -1
        small_old = valid and cls >= 0
        big_old = (valid and cls < 0 and self.big_log2.get(b, -1) >= 0
                   and ptr % block == 0)
        old = (classes[cls] if small_old
               else (1 << self.big_log2[b]) if big_old else 0)
        new_small = size <= classes[-1]
        new = (classes[self._class_of(size)] if new_small
               else max(_next_pow2(size), block))
        in_place = (((small_old and new_small) or (big_old and not new_small))
                    and new == old)
        return small_old or big_old, in_place

    def request(self, op, size, ptr) -> dict:
        """Serve one protocol round; returns per-thread ptr/ok/path/moved."""
        T = self.T
        is_alloc = [o in (OP_MALLOC, OP_CALLOC) for o in op]
        is_re = [o == OP_REALLOC for o in op]
        is_free = [o == OP_FREE for o in op]
        meta = [self._realloc_meta(ptr[t], size[t]) for t in range(T)]
        re_live = [is_re[t] and size[t] > 0 for t in range(T)]
        in_place = [re_live[t] and meta[t][1] for t in range(T)]
        moved = [re_live[t] and not meta[t][1] for t in range(T)]
        re_free0 = [is_re[t] and size[t] <= 0 and ptr[t] >= 0
                    for t in range(T)]

        m_active = [(is_alloc[t] and size[t] > 0) or moved[t]
                    for t in range(T)]
        mptrs, mpaths = self.malloc(
            [size[t] if m_active[t] else 0 for t in range(T)], m_active)
        mok = [m_active[t] and mptrs[t] >= 0 for t in range(T)]

        f_active = [is_free[t] or (moved[t] and meta[t][0] and mok[t])
                    or re_free0[t] for t in range(T)]
        fpaths = self.free(
            [ptr[t] if f_active[t] else -1 for t in range(T)], f_active)

        out = {"ptr": [], "ok": [], "path": [], "moved": []}
        for t in range(T):
            if (is_alloc[t] or moved[t]) and mok[t]:
                p = mptrs[t]
            elif in_place[t]:
                p = ptr[t]
            else:
                p = -1
            out["ptr"].append(p)
            out["ok"].append((is_alloc[t] and mok[t]) or in_place[t]
                             or (moved[t] and mok[t])
                             or ((is_free[t] or re_free0[t])
                                 and fpaths[t] in (0, 1)))
            if m_active[t]:
                out["path"].append(mpaths[t])
            elif is_free[t] or re_free0[t]:
                out["path"].append(fpaths[t])
            elif in_place[t]:
                out["path"].append(0)
            else:
                out["path"].append(-1)
            out["moved"].append(moved[t] and mok[t])
        return out


def make(config: dict) -> PimMalloc:
    """A fresh heap of one core of the configuration `config`."""
    return PimMalloc(heap_bytes=config["heap_bytes"],
                     num_threads=config["num_threads"],
                     size_classes=config["size_classes"],
                     block_bytes=config["block_bytes"],
                     cap=config["freelist_cap"])
