// Flush-ready records built in bulk: one call makes a run of a metric
// segment's `samplers.InterMetric` records, where the interpreter would
// run one class call (a frame and eight attribute stores) a record.
//
// Loaded with ctypes.PyDLL (veneur_tpu/samplers/record_builder.py): the
// caller holds the interpreter lock for the whole call, arguments and
// results are objects.  A translation unit of its own, because
// ingest_engine.cpp has to build on a host without the interpreter's
// headers.
//
// The record stays defined in Python alone: vn_record_layout reads the
// slots' offsets from the class's own member descriptors, and refuses
// any class that is not a plain eight-slot one.

#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <structmember.h>

#include <cstring>

namespace {

constexpr Py_ssize_t kSlots = 8;
// the order vn_build_records stores in; record_builder.SLOTS names them
enum Slot { kName, kTimestamp, kValue, kTags, kType, kMessage, kHostname,
            kSinks };

inline PyObject*& slot(PyObject* rec, Py_ssize_t offset) {
    return *reinterpret_cast<PyObject**>(reinterpret_cast<char*>(rec)
                                         + offset);
}

bool list_of(PyObject* o, Py_ssize_t n) {
    return PyList_CheckExact(o) && PyList_GET_SIZE(o) >= n;
}

bool ascii_str(PyObject* o) {
    return PyUnicode_CheckExact(o) && PyUnicode_IS_COMPACT_ASCII(o);
}

// base + suffix.  Metric names are ASCII all but always: two copies
// into a string of the known size, half the cost of PyUnicode_Concat,
// which serves every other pair (and raises TypeError for a base that
// is no str).
PyObject* joined(PyObject* base, PyObject* suffix, bool suffix_ascii) {
    if (!suffix_ascii || !ascii_str(base))
        return PyUnicode_Concat(base, suffix);
    const Py_ssize_t nb = PyUnicode_GET_LENGTH(base);
    const Py_ssize_t ns = PyUnicode_GET_LENGTH(suffix);
    PyObject* name = PyUnicode_New(nb + ns, 127);
    if (name == nullptr) return nullptr;
    Py_UCS1* to = PyUnicode_1BYTE_DATA(name);
    std::memcpy(to, PyUnicode_1BYTE_DATA(base), nb);
    std::memcpy(to + nb, PyUnicode_1BYTE_DATA(suffix), ns);
    return name;
}

}  // namespace

extern "C" {

// The byte offset of each of `names` (a tuple of eight str) in an
// instance of `cls`, into out[8].  0, or -1 with TypeError set when cls
// is anything but a direct subclass of object whose instances are
// exactly those eight writable object slots (no __dict__, no weakref
// list, no ninth slot): writing by offset into any other layout would
// corrupt it.
int vn_record_layout(PyObject* cls, PyObject* names, Py_ssize_t* out) {
    if (!PyType_Check(cls) || !PyTuple_CheckExact(names)
            || PyTuple_GET_SIZE(names) != kSlots) {
        PyErr_SetString(PyExc_TypeError,
                        "record layout: want a class and eight names");
        return -1;
    }
    PyTypeObject* tp = reinterpret_cast<PyTypeObject*>(cls);
    const Py_ssize_t head = static_cast<Py_ssize_t>(sizeof(PyObject));
    const Py_ssize_t word = static_cast<Py_ssize_t>(sizeof(PyObject*));
    if (tp->tp_base != &PyBaseObject_Type || tp->tp_itemsize != 0
            || tp->tp_dictoffset != 0 || tp->tp_weaklistoffset != 0
            || (tp->tp_flags & (Py_TPFLAGS_MANAGED_DICT
                                | Py_TPFLAGS_MANAGED_WEAKREF))
            || tp->tp_basicsize != head + kSlots * word) {
        PyErr_Format(PyExc_TypeError,
                     "record layout: %s is not a plain class of %zd slots",
                     tp->tp_name, kSlots);
        return -1;
    }
    PyObject* dict = PyType_GetDict(tp);
    if (dict == nullptr) return -1;
    unsigned seen = 0;
    for (Py_ssize_t i = 0; i < kSlots; i++) {
        PyObject* name = PyTuple_GET_ITEM(names, i);
        PyObject* descr = PyDict_GetItemWithError(dict, name);  // borrowed
        if (descr == nullptr || Py_TYPE(descr) != &PyMemberDescr_Type) {
            if (!PyErr_Occurred())
                PyErr_Format(PyExc_TypeError,
                             "record layout: %s has no slot %R",
                             tp->tp_name, name);
            Py_DECREF(dict);
            return -1;
        }
        const PyMemberDef* member =
            reinterpret_cast<PyMemberDescrObject*>(descr)->d_member;
        const Py_ssize_t at = member->offset - head;
        if (member->type != T_OBJECT_EX || (member->flags & READONLY)
                || at < 0 || at % word != 0 || at / word >= kSlots
                || (seen & (1u << (at / word)))) {
            PyErr_Format(PyExc_TypeError,
                         "record layout: slot %R of %s is not a writable "
                         "object slot of its own", name, tp->tp_name);
            Py_DECREF(dict);
            return -1;
        }
        seen |= 1u << (at / word);
        out[i] = member->offset;
    }
    Py_DECREF(dict);
    return 0;
}

// Records for rows lo..hi of a segment's columns, as a new list:
// name = bases[r] + suffix (bases[r] itself under an empty suffix),
// the shared timestamp and type, values[r], tags[r] (the row's own
// list, not a copy), "" for message and hostname, and sinks[r] (None
// where `sinks` is None).  `offsets` is vn_record_layout's answer for
// `cls`.  NULL with an exception set — and nothing built left behind —
// when a column is no list or too short, or a base is no str.
PyObject* vn_build_records(PyObject* cls, const Py_ssize_t* offsets,
                           PyObject* bases, PyObject* suffix,
                           PyObject* timestamp, PyObject* values,
                           PyObject* tags, PyObject* type, PyObject* sinks,
                           Py_ssize_t lo, Py_ssize_t hi) {
    if (!PyType_Check(cls) || !PyUnicode_Check(suffix)) {
        PyErr_SetString(PyExc_TypeError,
                        "build records: want a class and a str suffix");
        return nullptr;
    }
    if (lo < 0 || hi < lo || !list_of(bases, hi) || !list_of(values, hi)
            || !list_of(tags, hi)
            || (sinks != Py_None && !list_of(sinks, hi))) {
        PyErr_SetString(PyExc_ValueError,
                        "build records: columns must be lists that hold "
                        "rows lo..hi");
        return nullptr;
    }
    PyTypeObject* tp = reinterpret_cast<PyTypeObject*>(cls);
    const bool concat = PyUnicode_GET_LENGTH(suffix) > 0;
    const bool suffix_ascii = ascii_str(suffix);
    PyObject* empty = PyUnicode_New(0, 0);
    if (empty == nullptr) return nullptr;
    PyObject* out = PyList_New(hi - lo);
    for (Py_ssize_t r = lo; out != nullptr && r < hi; r++) {
        PyObject* base = PyList_GET_ITEM(bases, r);
        // the only steps that can fail come before the record exists,
        // so a record in `out` always has all eight slots
        PyObject* name = concat ? joined(base, suffix, suffix_ascii)
                                : Py_NewRef(base);
        PyObject* rec = name ? tp->tp_alloc(tp, 0) : nullptr;
        if (rec == nullptr) {
            Py_XDECREF(name);
            Py_CLEAR(out);      // frees the records built so far
            break;
        }
        slot(rec, offsets[kName]) = name;       // the new reference
        slot(rec, offsets[kTimestamp]) = Py_NewRef(timestamp);
        slot(rec, offsets[kValue]) = Py_NewRef(PyList_GET_ITEM(values, r));
        slot(rec, offsets[kTags]) = Py_NewRef(PyList_GET_ITEM(tags, r));
        slot(rec, offsets[kType]) = Py_NewRef(type);
        slot(rec, offsets[kMessage]) = Py_NewRef(empty);
        slot(rec, offsets[kHostname]) = Py_NewRef(empty);
        slot(rec, offsets[kSinks]) = Py_NewRef(
            sinks == Py_None ? Py_None : PyList_GET_ITEM(sinks, r));
        PyList_SET_ITEM(out, r - lo, rec);
    }
    Py_DECREF(empty);
    return out;
}

}  // extern "C"
