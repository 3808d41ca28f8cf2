// pt_flatten_doc — the native flatten of DocBatch's host encode.
//
// Walks one document's Change and Operation objects through the CPython
// API and returns the exact int columns that ops/encode.py::_flatten_rows
// builds for it: the sorted actors, per change a header (actor index, seq,
// dep count, op count), per dep an (actor index, seq) pair, per op a row of
// pt_encode_batch's layout (kind 0 insert, 1 delete, 2 mark, 6 map-register
// op, 7 makeList), the doc's mark attrs and map keys and string values in
// first-use order, and its four stream bounds.  pt_encode_batch
// (native.cpp) then schedules and scatters the rows as before.
//
// Wherever _flatten_rows would raise one of encode.py's _UNEXPRESSED, and
// wherever an object is not of the exact type the walk reads (a str
// subclass, a list where a tuple is expected, a bool where an int is), the
// walk declines: it returns None and the doc is flattened in Python, which
// keeps every case's behaviour as it was.
//
// Built apart from native.cpp, since it needs Python.h, and bound with
// ctypes.PyDLL, which holds the GIL across the call.  Attributes are read
// with PyObject_GetAttr, so the objects are left as they were, and nothing
// read survives the call.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <cstdint>
#include <cstring>
#include <vector>

namespace {

// A new reference, released at scope exit.
class Ref {
  public:
    explicit Ref(PyObject* p = nullptr) : p_(p) {}
    Ref(Ref&& other) noexcept : p_(other.p_) { other.p_ = nullptr; }
    ~Ref() { Py_XDECREF(p_); }
    Ref(const Ref&) = delete;
    Ref& operator=(const Ref&) = delete;
    PyObject* get() const { return p_; }

  private:
    PyObject* p_;
};

// Thrown where the doc must be flattened in Python; caught by
// pt_flatten_doc, which clears any Python error and returns None.
struct Decline {};

[[noreturn]] void decline() { throw Decline{}; }

PyObject* checked(PyObject* p) {
    if (p == nullptr) decline();
    return p;
}

// Attribute and string names, interned once per process.
struct Names {
    PyObject *actor, *seq, *deps, *ops;
    PyObject *action, *obj, *opid, *key, *elem_id, *insert, *value;
    PyObject *start, *end, *elem, *kind, *mark_type, *attrs;
    PyObject *url, *id;
    PyObject *set, *del, *add_mark, *remove_mark, *make_list, *make_map;
};

const Names* names() {
    static Names n;
    static bool ready = false;
    if (ready) return &n;
    PyObject** slots[] = {
        &n.actor, &n.seq, &n.deps, &n.ops,
        &n.action, &n.obj, &n.opid, &n.key, &n.elem_id, &n.insert, &n.value,
        &n.start, &n.end, &n.elem, &n.kind, &n.mark_type, &n.attrs,
        &n.url, &n.id,
        &n.set, &n.del, &n.add_mark, &n.remove_mark, &n.make_list, &n.make_map,
    };
    const char* text[] = {
        "actor", "seq", "deps", "ops",
        "action", "obj", "opid", "key", "elem_id", "insert", "value",
        "start", "end", "elem", "kind", "mark_type", "attrs",
        "url", "id",
        "set", "del", "addMark", "removeMark", "makeList", "makeMap",
    };
    for (size_t i = 0; i < sizeof(slots) / sizeof(slots[0]); ++i) {
        *slots[i] = checked(PyUnicode_InternFromString(text[i]));
    }
    ready = true;
    return &n;
}

enum Action { kSet, kDel, kAddMark, kRemoveMark, kMakeList, kMakeMap };

// The op kinds of pt_encode_batch's rows.
constexpr int64_t kInsert = 0, kDelete = 1, kMark = 2, kMap = 6, kList = 7;

// The value of an exact int, within int64.
int64_t exact_int(PyObject* o) {
    if (!PyLong_CheckExact(o)) decline();
    int overflow = 0;
    const long long v = PyLong_AsLongLongAndOverflow(o, &overflow);
    if (overflow) decline();
    return v;
}

void put(std::vector<int32_t>& out, int64_t v) {
    if (v < INT32_MIN || v > INT32_MAX) decline();  // array("i") overflows
    out.push_back(static_cast<int32_t>(v));
}

class Walker {
  public:
    Walker(PyObject* consts, Py_ssize_t attr_base, Py_ssize_t key_base)
        : n_(names()), attr_base_(attr_base), key_base_(key_base),
          index_(checked(PyDict_New())),
          attr_ids_(checked(PyDict_New())), attr_strs_(checked(PyList_New(0))),
          key_ids_(checked(PyDict_New())), key_strs_(checked(PyList_New(0))) {
        // (ROOT, HEAD, MARK_INDEX, _BK, ACTOR_BITS, MAX_ACTORS, MA_ADD,
        //  MA_REMOVE, VK_DELETED, VK_STR, VK_INT, VK_TRUE, VK_FALSE,
        //  VK_NULL, VK_OBJ): the constants of encode.py's walk
        if (!PyTuple_CheckExact(consts) || PyTuple_GET_SIZE(consts) != 15) decline();
        root_ = PyTuple_GET_ITEM(consts, 0);
        head_ = PyTuple_GET_ITEM(consts, 1);
        mark_index_ = PyTuple_GET_ITEM(consts, 2);
        bk_ = PyTuple_GET_ITEM(consts, 3);
        if (!PyDict_CheckExact(mark_index_) || !PyDict_CheckExact(bk_)) decline();
        int64_t* ints[] = {&bits_, &max_actors_, &ma_add_, &ma_remove_, &vk_deleted_,
                           &vk_str_, &vk_int_, &vk_true_, &vk_false_, &vk_null_,
                           &vk_obj_};
        for (int i = 0; i < 11; ++i) *ints[i] = exact_int(PyTuple_GET_ITEM(consts, 4 + i));
        if (bits_ < 0 || bits_ > 30) decline();
    }

    // The doc's changes in delivery order, then its columns as
    // _flatten_doc hands them to _Flat.add_doc: (changes, actors, bounds,
    // heads, deps, rows, attr_strs, key_strs).
    PyObject* walk(PyObject* queues) {
        Ref all(delivered(queues));
        PyObject* changes = all.get();
        const Py_ssize_t n = PyList_GET_SIZE(changes);
        ch_actor_.reserve(n);
        for (Py_ssize_t i = 0; i < n; ++i) {
            ch_actor_.emplace_back(get(PyList_GET_ITEM(changes, i), n_->actor));
        }
        Ref actors(sorted_actors());
        heads_.reserve(4 * n);
        for (Py_ssize_t i = 0; i < n; ++i) {
            // the next changes' and (in walk_change) ops' objects are
            // fetched ahead: ~5% off the walk on a TPU v5e host
            if (i + 2 < n) __builtin_prefetch(PyList_GET_ITEM(changes, i + 2));
            walk_change(PyList_GET_ITEM(changes, i), ch_actor_[i].get());
        }
        const int64_t n_map = n_ops_ - n_ins_ - n_del_ - n_mark_;
        Ref bounds(checked(Py_BuildValue("(LLLL)", static_cast<long long>(n_ins_),
                                         static_cast<long long>(n_del_),
                                         static_cast<long long>(n_mark_),
                                         static_cast<long long>(n_map))));
        Ref heads(ints(heads_)), deps(ints(deps_)), rows(ints(rows_));
        return checked(PyTuple_Pack(8, changes, actors.get(), bounds.get(), heads.get(),
                                    deps.get(), rows.get(), attr_strs_.get(),
                                    key_strs_.get()));
    }

  private:
    // [ch for log in queues.values() for ch in log] of a dict of lists
    static PyObject* delivered(PyObject* queues) {
        if (!PyDict_CheckExact(queues)) decline();
        Py_ssize_t n = 0, pos = 0;
        PyObject *actor, *log;
        while (PyDict_Next(queues, &pos, &actor, &log)) {
            if (!PyList_CheckExact(log)) decline();
            n += PyList_GET_SIZE(log);
        }
        Ref changes(checked(PyList_New(n)));
        Py_ssize_t at = 0;
        pos = 0;
        while (PyDict_Next(queues, &pos, &actor, &log)) {
            for (Py_ssize_t i = 0; i < PyList_GET_SIZE(log); ++i) {
                PyObject* ch = PyList_GET_ITEM(log, i);
                Py_INCREF(ch);
                PyList_SET_ITEM(changes.get(), at++, ch);
            }
        }
        PyObject* out = changes.get();
        Py_INCREF(out);
        return out;
    }

    static PyObject* ints(const std::vector<int32_t>& v) {
        return checked(PyBytes_FromStringAndSize(
            reinterpret_cast<const char*>(v.data()),
            static_cast<Py_ssize_t>(v.size() * sizeof(int32_t))));
    }

    PyObject* get(PyObject* o, PyObject* name) const {
        return checked(PyObject_GetAttr(o, name));
    }

    // sorted({ch.actor for ch in changes}), and the index of each from 1
    PyObject* sorted_actors() {
        Ref seen(checked(PySet_New(nullptr)));
        for (const Ref& actor : ch_actor_) {
            if (!PyUnicode_CheckExact(actor.get())) decline();
            if (PySet_Add(seen.get(), actor.get()) < 0) decline();
        }
        Ref actors(checked(PySequence_List(seen.get())));
        if (PyList_Sort(actors.get()) < 0) decline();
        const Py_ssize_t n = PyList_GET_SIZE(actors.get());
        if (n > max_actors_) decline();
        for (Py_ssize_t i = 0; i < n; ++i) {
            PyObject* actor = PyList_GET_ITEM(actors.get(), i);
            Ref at(checked(PyLong_FromSsize_t(i + 1)));
            if (PyDict_SetItem(index_.get(), actor, at.get()) < 0) decline();
            if (n <= kScanActors) scan_.push_back({PyObject_Hash(actor), actor, i + 1});
        }
        PyObject* out = actors.get();
        Py_INCREF(out);
        return out;
    }

    // index[actor]: the actor must have sent a change.  A few actors are
    // scanned (hash, then length, kind and code points: str equality),
    // more looked up in the dict.
    int64_t actor_index(PyObject* actor) const {
        if (!scan_.empty() && PyUnicode_CheckExact(actor)) {
            const Py_hash_t h = PyObject_Hash(actor);  // cached in the str
            for (const Scan& e : scan_) {
                if (e.actor == actor) return e.index;
                if (e.hash == h && str_equal(e.actor, actor)) return e.index;
            }
            decline();
        }
        PyObject* at = PyDict_GetItemWithError(index_.get(), actor);
        if (at == nullptr) decline();
        return PyLong_AsLongLong(at);
    }

    static bool str_equal(PyObject* a, PyObject* b) {
        const Py_ssize_t n = PyUnicode_GET_LENGTH(a);
        return n == PyUnicode_GET_LENGTH(b) && PyUnicode_KIND(a) == PyUnicode_KIND(b) &&
               std::memcmp(PyUnicode_DATA(a), PyUnicode_DATA(b),
                           static_cast<size_t>(n) * PyUnicode_KIND(a)) == 0;
    }

    // (ctr << ACTOR_BITS) | index[actor] of an exact (int, actor) pair
    int64_t pack(PyObject* id) const {
        if (!PyTuple_CheckExact(id) || PyTuple_GET_SIZE(id) != 2) decline();
        const int64_t ctr = exact_int(PyTuple_GET_ITEM(id, 0));
        // beyond this the packed id cannot fit int32 whatever the index
        if (ctr > (int64_t{1} << 32) || ctr < -(int64_t{1} << 32)) decline();
        const uint64_t shifted = static_cast<uint64_t>(ctr) << bits_;
        return static_cast<int64_t>(shifted) | actor_index(PyTuple_GET_ITEM(id, 1));
    }

    // _string_id: the string's id among ``strs`` in first-use order
    static int64_t string_id(PyObject* s, PyObject* ids, PyObject* strs) {
        PyObject* at = PyDict_GetItemWithError(ids, s);
        if (at != nullptr) return PyLong_AsLongLong(at);
        if (PyErr_Occurred()) decline();
        const Py_ssize_t id = PyList_GET_SIZE(strs);
        Ref g(checked(PyLong_FromSsize_t(id)));
        if (PyDict_SetItem(ids, s, g.get()) < 0 || PyList_Append(strs, s) < 0) decline();
        return id;
    }

    int64_t key_id(PyObject* s) const {
        return key_base_ + string_id(s, key_ids_.get(), key_strs_.get());
    }

    Action action_of(PyObject* action) const {
        if (!PyUnicode_CheckExact(action)) decline();
        const Names& n = *n_;
        const std::pair<PyObject*, Action> known[] = {
            {n.set, kSet}, {n.del, kDel}, {n.add_mark, kAddMark},
            {n.remove_mark, kRemoveMark}, {n.make_list, kMakeList},
            {n.make_map, kMakeMap},
        };
        for (const auto& [name, code] : known)
            if (action == name) return code;
        for (const auto& [name, code] : known)
            if (PyUnicode_Compare(action, name) == 0) return code;
        decline();  // any other action: _map_row raises
    }

    // _BK[kind] or MARK_INDEX[name]: a lookup in one of the walk's tables
    static int64_t lookup(PyObject* table, PyObject* k) {
        PyObject* v = PyDict_GetItemWithError(table, k);
        if (v == nullptr) decline();
        return exact_int(v);
    }

    void walk_change(PyObject* ch, PyObject* actor) {
        Ref seq(get(ch, n_->seq)), deps(get(ch, n_->deps)), ops(get(ch, n_->ops));
        if (!PyDict_CheckExact(deps.get())) decline();
        if (!PyList_CheckExact(ops.get()) && !PyTuple_CheckExact(ops.get())) decline();
        const Py_ssize_t n_ops = PySequence_Fast_GET_SIZE(ops.get());
        put(heads_, actor_index(actor));
        put(heads_, exact_int(seq.get()));
        put(heads_, PyDict_GET_SIZE(deps.get()));
        put(heads_, n_ops);
        Py_ssize_t pos = 0;
        PyObject *a, *s;
        while (PyDict_Next(deps.get(), &pos, &a, &s)) {
            put(deps_, actor_index(a));
            put(deps_, exact_int(s));
        }
        n_ops_ += n_ops;
        for (Py_ssize_t i = 0; i < n_ops; ++i) {
            // held, in case a lookup's __eq__ changes the list under us
            if (PySequence_Fast_GET_SIZE(ops.get()) != n_ops) decline();
            if (i + 1 < n_ops) __builtin_prefetch(PySequence_Fast_GET_ITEM(ops.get(), i + 1));
            Ref op(PySequence_Fast_GET_ITEM(ops.get(), i));
            Py_INCREF(op.get());
            walk_op(op.get());
        }
    }

    void walk_op(PyObject* op) {
        const Names& n = *n_;
        Ref action_obj(get(op, n.action)), obj(get(op, n.obj)), opid(get(op, n.opid));
        const Action action = action_of(action_obj.get());
        const int64_t popid = pack(opid.get());
        const int64_t pobj = obj.get() == root_ ? -1 : pack(obj.get());
        if (action == kSet) {
            Ref insert(get(op, n.insert));
            if (insert.get() == Py_True) {
                Ref e(get(op, n.elem_id)), value(get(op, n.value));
                const int64_t ref = e.get() == head_ ? 0 : pack(e.get());
                // ord(): exactly one code point
                if (!PyUnicode_CheckExact(value.get()) || PyUnicode_GET_LENGTH(value.get()) != 1)
                    decline();
                row({kInsert, pobj, popid, ref, PyUnicode_READ_CHAR(value.get(), 0)});
                ++n_ins_;
                return;
            }
            if (insert.get() != Py_False && insert.get() != Py_None) decline();
        }
        if (action == kAddMark || action == kRemoveMark) {
            walk_mark(op, action, pobj, popid);
            return;
        }
        Ref key(get(op, n.key));
        if (action == kDel && key.get() == Py_None) {
            Ref e(get(op, n.elem_id));
            row({kDelete, pobj, popid, pack(e.get())});
            ++n_del_;
            return;
        }
        walk_map(op, action, key.get(), pobj, popid);
    }

    void walk_mark(PyObject* op, Action action, int64_t pobj, int64_t popid) {
        const Names& n = *n_;
        Ref start(get(op, n.start)), end(get(op, n.end));
        Ref se(get(start.get(), n.elem)), ee(get(end.get(), n.elem));
        Ref start_kind(get(start.get(), n.kind)), end_kind(get(end.get(), n.kind));
        Ref mark_type(get(op, n.mark_type)), attrs(get(op, n.attrs));
        int64_t attr = 0;
        if (attrs.get() != Py_None) {
            if (!PyDict_CheckExact(attrs.get())) decline();
            // key-presence, not truthiness: empty url/id is a value
            PyObject* value = PyDict_GetItemWithError(attrs.get(), n.url);
            if (value == nullptr && !PyErr_Occurred())
                value = PyDict_GetItemWithError(attrs.get(), n.id);
            if (PyErr_Occurred()) decline();
            if (value != nullptr) {
                if (!PyUnicode_CheckExact(value)) decline();
                attr = attr_base_ + string_id(value, attr_ids_.get(), attr_strs_.get()) + 1;
            }
        }
        row({kMark, pobj, popid, action == kAddMark ? ma_add_ : ma_remove_,
             lookup(mark_index_, mark_type.get()),
             lookup(bk_, start_kind.get()), se.get() == Py_None ? 0 : pack(se.get()),
             lookup(bk_, end_kind.get()), ee.get() == Py_None ? 0 : pack(ee.get()),
             attr});
        ++n_mark_;
    }

    // _map_row: an op on a map object
    void walk_map(PyObject* op, Action action, PyObject* key, int64_t pobj, int64_t popid) {
        if (!PyUnicode_CheckExact(key)) decline();
        const int64_t k = key_id(key);
        if (action == kMakeList) {
            row({kList, pobj, popid, k});
        } else if (action == kMakeMap) {
            row({kMap, pobj, popid, k, vk_obj_, popid});
        } else if (action == kDel) {
            Ref e(get(op, n_->elem_id));
            if (e.get() != Py_None) decline();
            row({kMap, pobj, popid, k, vk_deleted_, 0});
        } else {  // kSet: a register value of _encode_value's kinds
            Ref value(get(op, n_->value));
            PyObject* v = value.get();
            if (v == Py_True || v == Py_False) {
                row({kMap, pobj, popid, k, v == Py_True ? vk_true_ : vk_false_, 0});
            } else if (v == Py_None) {
                row({kMap, pobj, popid, k, vk_null_, 0});
            } else if (PyUnicode_CheckExact(v)) {
                row({kMap, pobj, popid, k, vk_str_, key_id(v) + 1});
            } else {
                const int64_t i = exact_int(v);
                if (i < INT32_MIN || i > INT32_MAX) decline();
                row({kMap, pobj, popid, k, vk_int_, i});
            }
        }
    }

    void row(std::initializer_list<int64_t> values) {
        for (const int64_t v : values) put(rows_, v);
    }

    // actors scanned rather than looked up in index_
    static constexpr Py_ssize_t kScanActors = 8;
    struct Scan {
        Py_hash_t hash;
        PyObject* actor;  // borrowed from the sorted actor list
        int64_t index;
    };

    const Names* n_;
    PyObject *root_, *head_, *mark_index_, *bk_;  // borrowed from the constants
    int64_t bits_, max_actors_, ma_add_, ma_remove_, vk_deleted_, vk_str_, vk_int_,
        vk_true_, vk_false_, vk_null_, vk_obj_;
    const int64_t attr_base_, key_base_;
    Ref index_, attr_ids_, attr_strs_, key_ids_, key_strs_;
    std::vector<Ref> ch_actor_;  // each change's actor, in order
    std::vector<Scan> scan_;
    std::vector<int32_t> heads_, deps_, rows_;
    int64_t n_ins_ = 0, n_del_ = 0, n_mark_ = 0, n_ops_ = 0;
};

}  // namespace

extern "C" {

// One doc's flatten: ``queues`` (the doc's change logs, actor -> list of
// Change), ``consts`` (Walker's constants), the batch's attr and key
// string counts so far.  Returns the doc's changes and columns, or None
// where the doc is left to the Python flatten.
PyObject* pt_flatten_doc(PyObject* queues, PyObject* consts, Py_ssize_t attr_base,
                         Py_ssize_t key_base) {
    try {
        Walker walker(consts, attr_base, key_base);
        return walker.walk(queues);
    } catch (const Decline&) {
        if (PyErr_Occurred() && !PyErr_ExceptionMatches(PyExc_Exception)) {
            return nullptr;  // KeyboardInterrupt and the like go on up
        }
        PyErr_Clear();
        Py_RETURN_NONE;
    }
}

}  // extern "C"
