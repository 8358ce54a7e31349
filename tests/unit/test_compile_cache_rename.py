"""The persistent compile cache's entries are written through a rename (``utils/compile_cache.py``): six tier-1 workers
share ``tests/.jax_cache``, and a worker that read an entry another was writing in place took a segmentation fault."""

import os

import pytest

from deepspeed_tpu.utils import compile_cache


@pytest.mark.parametrize("case", ["whole", "torn_write", "there_already", "bounded"])
def test_an_entry_is_whole_or_absent(case, tmp_path, monkeypatch):
    from jax._src import lru_cache

    assert compile_cache.write_entries_through_a_rename() and compile_cache.write_entries_through_a_rename()  # idempotent
    assert lru_cache.LRUCache.put.through_a_rename
    cache = lru_cache.LRUCache(str(tmp_path), max_size=1 << 20 if case == "bounded" else -1)
    entry = tmp_path / "k-cache"
    renamed = []
    replace = os.replace
    monkeypatch.setattr(os, "replace", lambda a, b: (renamed.append((os.path.basename(a), os.path.basename(b))), replace(a, b))[1])
    if case == "torn_write":  # the disk fills half way: the reader must find nothing, not half an executable
        def half(self, data):
            with open(self, "wb") as f:
                f.write(data[:3])
            raise OSError("no space left on device")
        monkeypatch.setattr(type(cache.path / "k"), "write_bytes", half)
        with pytest.raises(OSError):
            cache.put("k", b"0123456789")
        assert not entry.exists() and cache.get("k") is None and [p.name for p in tmp_path.iterdir() if p.suffix == ".tmp"] == []
        return
    if case == "there_already":
        entry.write_bytes(b"first")
    cache.put("k", b"0123456789")
    assert cache.get("k") == (b"first" if case == "there_already" else b"0123456789")
    assert [p.name for p in tmp_path.iterdir() if p.suffix == ".tmp"] == []
    # under a size bound JAX's own put runs, under the cache's lock, and renames nothing; an entry that is there is left alone
    assert renamed == ([(f"k.{os.getpid()}.tmp", "k-cache")] if case == "whole" else [])
