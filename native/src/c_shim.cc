// libquest_tpu.so — C-ABI shim over the quest_tpu Python framework.
//
// Embeds CPython: every C call marshals into the corresponding
// quest_tpu.api function (include/QuEST.h documents the covered
// surface). Registers are Python objects behind integer handles; the
// C-side Qureg/QuESTEnv structs carry only the handle plus the
// introspection fields user code reads directly.
//
// Error contract: a Python-side QuESTError prints the reference-style
// message and exits(1) — the reference's default fatal
// invalidQuESTInputError behavior (QuEST_validation.c:126-137).
//
// Build: native/Makefile target `cshim` (links libpython).

#include <Python.h>

#include <dlfcn.h>

#include <cstdio>
#include <cstdlib>
#include <map>
#include <string>

#include "../../include/QuEST.h"

namespace {

PyObject *g_qt = nullptr;                 // quest_tpu module
std::map<int, PyObject *> g_objects;      // handle -> env/qureg
int g_next_handle = 1;
PyObject *g_first_env = nullptr;          // for implicit-env C calls

void fatal_py(const char *where) {
    std::fprintf(stderr, "QuEST-TPU shim error in %s:\n", where);
    PyErr_Print();
    std::exit(1);
}

void ensure_python() {
    if (g_qt != nullptr) return;
    if (!Py_IsInitialized()) Py_Initialize();
    // backend selection before jax import: QUEST_TPU_C_PLATFORM only,
    // default cpu. Deliberately NOT honoring JAX_PLATFORMS: an embedded
    // user binary takes a chip only when explicitly asked
    // (include/QuEST.h documents the knob).
    int rc = PyRun_SimpleString(
        "import os\n"
        "_plat = os.environ.get('QUEST_TPU_C_PLATFORM') or 'cpu'\n"
        "os.environ['JAX_PLATFORMS'] = _plat\n"
        "import jax\n"
        "jax.config.update('jax_platforms', _plat)\n"
        "jax.config.update('jax_enable_x64', True)\n");
    if (rc != 0) fatal_py("python bootstrap");
    // the shim ships inside quest_tpu/native/ — put the package root
    // (two directories up from this .so) on sys.path so an embedded
    // interpreter finds the framework without an installed wheel
    Dl_info info;
    if (dladdr(reinterpret_cast<void *>(&ensure_python), &info)
        && info.dli_fname != nullptr) {
        std::string root(info.dli_fname);
        for (int up = 0; up < 3; ++up) {
            auto cut = root.find_last_of('/');
            if (cut == std::string::npos) break;
            root.erase(cut);
        }
        // no string-spliced code: a path containing quotes must not
        // become a syntax error
        PyObject *path = PySys_GetObject("path");  // borrowed
        PyObject *entry = PyUnicode_FromString(root.c_str());
        if (path == nullptr || entry == nullptr
            || PyList_Insert(path, 0, entry) != 0)
            fatal_py("sys.path bootstrap");
        Py_DECREF(entry);
    }
    g_qt = PyImport_ImportModule("quest_tpu");
    if (g_qt == nullptr) fatal_py("import quest_tpu");
}

int store(PyObject *obj) {
    int h = g_next_handle++;
    g_objects[h] = obj;
    return h;
}

PyObject *lookup(int handle, const char *where) {
    auto it = g_objects.find(handle);
    if (it == g_objects.end()) {
        std::fprintf(stderr,
                     "QuEST-TPU shim: stale/unknown handle %d in %s\n",
                     handle, where);
        std::exit(1);
    }
    return it->second;
}

// call qt.<name>(...) with a ready argument tuple; returns new ref
PyObject *call(const char *name, PyObject *args) {
    ensure_python();
    PyObject *fn = PyObject_GetAttrString(g_qt, name);
    if (fn == nullptr) fatal_py(name);
    PyObject *out = PyObject_CallObject(fn, args);
    Py_DECREF(fn);
    Py_XDECREF(args);
    if (out == nullptr) fatal_py(name);
    return out;
}

void call_void(const char *name, PyObject *args) {
    Py_DECREF(call(name, args));
}

double call_real(const char *name, PyObject *args) {
    PyObject *out = call(name, args);
    double v = PyFloat_AsDouble(out);
    Py_DECREF(out);
    if (PyErr_Occurred()) fatal_py(name);
    return v;
}

long long call_int(const char *name, PyObject *args) {
    PyObject *out = call(name, args);
    long long v = PyLong_AsLongLong(out);
    Py_DECREF(out);
    if (PyErr_Occurred()) fatal_py(name);
    return v;
}

Complex call_complex(const char *name, PyObject *args) {
    PyObject *out = call(name, args);
    Py_complex c = PyComplex_AsCComplex(out);
    Py_DECREF(out);
    if (PyErr_Occurred()) fatal_py(name);
    return Complex{c.real, c.imag};
}

PyObject *py_qureg(Qureg q) { return lookup(q.handle, "qureg"); }
PyObject *py_env(QuESTEnv e) { return lookup(e.handle, "env"); }

PyObject *py_complex(Complex c) {
    return PyComplex_FromDoubles(c.real, c.imag);
}

PyObject *py_int_list(const int *xs, int n) {
    PyObject *lst = PyList_New(n);
    for (int i = 0; i < n; ++i)
        PyList_SET_ITEM(lst, i, PyLong_FromLong(xs[i]));
    return lst;
}

// dim x dim complex matrix as list-of-lists from separate re/im tables
template <typename Get>
PyObject *py_matrix(int dim, Get at) {
    PyObject *rows = PyList_New(dim);
    for (int r = 0; r < dim; ++r) {
        PyObject *row = PyList_New(dim);
        for (int c = 0; c < dim; ++c)
            PyList_SET_ITEM(row, c, at(r, c));
        PyList_SET_ITEM(rows, r, row);
    }
    return rows;
}

PyObject *py_m2(ComplexMatrix2 u) {
    return py_matrix(2, [&](int r, int c) {
        return PyComplex_FromDoubles(u.real[r][c], u.imag[r][c]);
    });
}

PyObject *py_m4(ComplexMatrix4 u) {
    return py_matrix(4, [&](int r, int c) {
        return PyComplex_FromDoubles(u.real[r][c], u.imag[r][c]);
    });
}

PyObject *py_mn(ComplexMatrixN u) {
    int dim = 1 << u.numQubits;
    return py_matrix(dim, [&](int r, int c) {
        return PyComplex_FromDoubles(u.real[r][c], u.imag[r][c]);
    });
}

PyObject *py_axis(Vector v) {
    return Py_BuildValue("(ddd)", v.x, v.y, v.z);
}

}  // namespace

extern "C" {

QuESTEnv createQuESTEnv(void) {
    ensure_python();
    PyObject *env = call("createQuESTEnv", nullptr);
    if (g_first_env == nullptr) g_first_env = env;
    QuESTEnv out;
    out.handle = store(env);
    out.numRanks = 1;
    return out;
}

void destroyQuESTEnv(QuESTEnv env) {
    PyObject *e = py_env(env);
    call_void("destroyQuESTEnv", Py_BuildValue("(O)", e));
    g_objects.erase(env.handle);
    if (g_first_env == e) g_first_env = nullptr;
    Py_DECREF(e);
}

void reportQuESTEnv(QuESTEnv env) {
    call_void("reportQuESTEnv", Py_BuildValue("(O)", py_env(env)));
}

void seedQuEST(unsigned long int *seedArray, int numSeeds) {
    ensure_python();
    PyObject *seeds = PyList_New(numSeeds);
    for (int i = 0; i < numSeeds; ++i)
        PyList_SET_ITEM(seeds, i,
                        PyLong_FromUnsignedLong(seedArray[i]));
    // framework spelling: seedQuEST(env, seeds); the C API's implicit
    // global env is the program's first-created env (single-env
    // programs, the reference's own model)
    if (g_first_env == nullptr) {
        std::fprintf(stderr, "seedQuEST before createQuESTEnv\n");
        std::exit(1);
    }
    call_void("seedQuEST", Py_BuildValue("(ON)", g_first_env, seeds));
}

static Qureg make_qureg(const char *ctor, int numQubits, QuESTEnv env) {
    PyObject *q = call(ctor, Py_BuildValue("(iO)", numQubits, py_env(env)));
    Qureg out;
    out.handle = store(q);
    out.numQubitsRepresented = numQubits;
    PyObject *isdm = PyObject_GetAttrString(q, "is_density_matrix");
    if (isdm == nullptr) fatal_py(ctor);
    out.isDensityMatrix = PyObject_IsTrue(isdm);
    Py_DECREF(isdm);
    out.numQubitsInStateVec =
        out.isDensityMatrix ? 2 * numQubits : numQubits;
    out.numAmpsTotal = 1LL << out.numQubitsInStateVec;
    return out;
}

Qureg createQureg(int numQubits, QuESTEnv env) {
    return make_qureg("createQureg", numQubits, env);
}

Qureg createDensityQureg(int numQubits, QuESTEnv env) {
    return make_qureg("createDensityQureg", numQubits, env);
}

void destroyQureg(Qureg qureg, QuESTEnv env) {
    PyObject *q = py_qureg(qureg);
    call_void("destroyQureg", Py_BuildValue("(OO)", q, py_env(env)));
    g_objects.erase(qureg.handle);
    Py_DECREF(q);
}

void reportQuregParams(Qureg qureg) {
    call_void("reportQuregParams", Py_BuildValue("(O)", py_qureg(qureg)));
}

void reportStateToScreen(Qureg qureg, QuESTEnv env, int reportRank) {
    call_void("reportStateToScreen",
              Py_BuildValue("(OOi)", py_qureg(qureg), py_env(env),
                            reportRank));
}

ComplexMatrixN createComplexMatrixN(int numQubits) {
    int dim = 1 << numQubits;
    ComplexMatrixN m;
    m.numQubits = numQubits;
    m.real = static_cast<qreal **>(std::calloc(dim, sizeof(qreal *)));
    m.imag = static_cast<qreal **>(std::calloc(dim, sizeof(qreal *)));
    for (int r = 0; r < dim; ++r) {
        m.real[r] = static_cast<qreal *>(std::calloc(dim, sizeof(qreal)));
        m.imag[r] = static_cast<qreal *>(std::calloc(dim, sizeof(qreal)));
    }
    return m;
}

void destroyComplexMatrixN(ComplexMatrixN m) {
    int dim = 1 << m.numQubits;
    for (int r = 0; r < dim; ++r) {
        std::free(m.real[r]);
        std::free(m.imag[r]);
    }
    std::free(m.real);
    std::free(m.imag);
}

void initZeroState(Qureg q) {
    call_void("initZeroState", Py_BuildValue("(O)", py_qureg(q)));
}
void initPlusState(Qureg q) {
    call_void("initPlusState", Py_BuildValue("(O)", py_qureg(q)));
}
void initDebugState(Qureg q) {
    call_void("initDebugState", Py_BuildValue("(O)", py_qureg(q)));
}
void initClassicalState(Qureg q, long long int stateInd) {
    call_void("initClassicalState",
              Py_BuildValue("(OL)", py_qureg(q), stateInd));
}
void initPureState(Qureg q, Qureg pure) {
    call_void("initPureState",
              Py_BuildValue("(OO)", py_qureg(q), py_qureg(pure)));
}

#define SHIM_1Q(name) \
    void name(Qureg q, int t) { \
        call_void(#name, Py_BuildValue("(Oi)", py_qureg(q), t)); }
SHIM_1Q(hadamard)
SHIM_1Q(pauliX)
SHIM_1Q(pauliY)
SHIM_1Q(pauliZ)
SHIM_1Q(sGate)
SHIM_1Q(tGate)
#undef SHIM_1Q

#define SHIM_1Q_ANGLE(name) \
    void name(Qureg q, int t, qreal angle) { \
        call_void(#name, Py_BuildValue("(Oid)", py_qureg(q), t, angle)); }
SHIM_1Q_ANGLE(phaseShift)
SHIM_1Q_ANGLE(rotateX)
SHIM_1Q_ANGLE(rotateY)
SHIM_1Q_ANGLE(rotateZ)
#undef SHIM_1Q_ANGLE

void rotateAroundAxis(Qureg q, int t, qreal angle, Vector axis) {
    call_void("rotateAroundAxis",
              Py_BuildValue("(OidN)", py_qureg(q), t, angle, py_axis(axis)));
}

void compactUnitary(Qureg q, int t, Complex alpha, Complex beta) {
    call_void("compactUnitary",
              Py_BuildValue("(OiNN)", py_qureg(q), t, py_complex(alpha),
                            py_complex(beta)));
}

void unitary(Qureg q, int t, ComplexMatrix2 u) {
    call_void("unitary",
              Py_BuildValue("(OiN)", py_qureg(q), t, py_m2(u)));
}

#define SHIM_C1Q(name) \
    void name(Qureg q, int c, int t) { \
        call_void(#name, Py_BuildValue("(Oii)", py_qureg(q), c, t)); }
SHIM_C1Q(controlledNot)
SHIM_C1Q(controlledPauliY)
SHIM_C1Q(controlledPhaseFlip)
SHIM_C1Q(swapGate)
#undef SHIM_C1Q

#define SHIM_C1Q_ANGLE(name) \
    void name(Qureg q, int c, int t, qreal angle) { \
        call_void(#name, Py_BuildValue("(Oiid)", py_qureg(q), c, t, angle)); }
SHIM_C1Q_ANGLE(controlledPhaseShift)
SHIM_C1Q_ANGLE(controlledRotateX)
SHIM_C1Q_ANGLE(controlledRotateY)
SHIM_C1Q_ANGLE(controlledRotateZ)
#undef SHIM_C1Q_ANGLE

void controlledRotateAroundAxis(Qureg q, int c, int t, qreal angle,
                                Vector axis) {
    call_void("controlledRotateAroundAxis",
              Py_BuildValue("(OiidN)", py_qureg(q), c, t, angle,
                            py_axis(axis)));
}

void controlledCompactUnitary(Qureg q, int c, int t, Complex alpha,
                              Complex beta) {
    call_void("controlledCompactUnitary",
              Py_BuildValue("(OiiNN)", py_qureg(q), c, t,
                            py_complex(alpha), py_complex(beta)));
}

void controlledUnitary(Qureg q, int c, int t, ComplexMatrix2 u) {
    call_void("controlledUnitary",
              Py_BuildValue("(OiiN)", py_qureg(q), c, t, py_m2(u)));
}

void multiControlledPhaseFlip(Qureg q, int *ctrls, int n) {
    call_void("multiControlledPhaseFlip",
              Py_BuildValue("(ON)", py_qureg(q), py_int_list(ctrls, n)));
}

void multiControlledPhaseShift(Qureg q, int *ctrls, int n, qreal angle) {
    call_void("multiControlledPhaseShift",
              Py_BuildValue("(ONd)", py_qureg(q), py_int_list(ctrls, n),
                            angle));
}

void multiControlledUnitary(Qureg q, int *ctrls, int n, int t,
                            ComplexMatrix2 u) {
    call_void("multiControlledUnitary",
              Py_BuildValue("(ONiN)", py_qureg(q), py_int_list(ctrls, n),
                            t, py_m2(u)));
}

void twoQubitUnitary(Qureg q, int t1, int t2, ComplexMatrix4 u) {
    call_void("twoQubitUnitary",
              Py_BuildValue("(OiiN)", py_qureg(q), t1, t2, py_m4(u)));
}

void multiQubitUnitary(Qureg q, int *targs, int numTargs, ComplexMatrixN u) {
    call_void("multiQubitUnitary",
              Py_BuildValue("(ONN)", py_qureg(q),
                            py_int_list(targs, numTargs), py_mn(u)));
}

#define SHIM_NOISE(name) \
    void name(Qureg q, int t, qreal prob) { \
        call_void(#name, Py_BuildValue("(Oid)", py_qureg(q), t, prob)); }
SHIM_NOISE(mixDephasing)
SHIM_NOISE(mixDepolarising)
SHIM_NOISE(mixDamping)
#undef SHIM_NOISE

int measure(Qureg q, int t) {
    return static_cast<int>(
        call_int("measure", Py_BuildValue("(Oi)", py_qureg(q), t)));
}

int measureWithStats(Qureg q, int t, qreal *outcomeProb) {
    PyObject *out = call("measureWithStats",
                         Py_BuildValue("(Oi)", py_qureg(q), t));
    int outcome = static_cast<int>(
        PyLong_AsLongLong(PyTuple_GetItem(out, 0)));
    *outcomeProb = PyFloat_AsDouble(PyTuple_GetItem(out, 1));
    Py_DECREF(out);
    if (PyErr_Occurred()) fatal_py("measureWithStats");
    return outcome;
}

qreal collapseToOutcome(Qureg q, int t, int outcome) {
    return call_real("collapseToOutcome",
                     Py_BuildValue("(Oii)", py_qureg(q), t, outcome));
}

qreal calcTotalProb(Qureg q) {
    return call_real("calcTotalProb", Py_BuildValue("(O)", py_qureg(q)));
}

qreal calcProbOfOutcome(Qureg q, int t, int outcome) {
    return call_real("calcProbOfOutcome",
                     Py_BuildValue("(Oii)", py_qureg(q), t, outcome));
}

qreal calcPurity(Qureg q) {
    return call_real("calcPurity", Py_BuildValue("(O)", py_qureg(q)));
}

qreal calcFidelity(Qureg q, Qureg pure) {
    return call_real("calcFidelity",
                     Py_BuildValue("(OO)", py_qureg(q), py_qureg(pure)));
}

Complex calcInnerProduct(Qureg bra, Qureg ket) {
    return call_complex("calcInnerProduct",
                        Py_BuildValue("(OO)", py_qureg(bra), py_qureg(ket)));
}

Complex getAmp(Qureg q, long long int index) {
    return call_complex("getAmp",
                        Py_BuildValue("(OL)", py_qureg(q), index));
}

Complex getDensityAmp(Qureg q, long long int row, long long int col) {
    return call_complex("getDensityAmp",
                        Py_BuildValue("(OLL)", py_qureg(q), row, col));
}

qreal getRealAmp(Qureg q, long long int index) {
    return call_real("getRealAmp",
                     Py_BuildValue("(OL)", py_qureg(q), index));
}

qreal getImagAmp(Qureg q, long long int index) {
    return call_real("getImagAmp",
                     Py_BuildValue("(OL)", py_qureg(q), index));
}

qreal getProbAmp(Qureg q, long long int index) {
    return call_real("getProbAmp",
                     Py_BuildValue("(OL)", py_qureg(q), index));
}

int getNumQubits(Qureg q) {
    return static_cast<int>(
        call_int("getNumQubits", Py_BuildValue("(O)", py_qureg(q))));
}

long long int getNumAmps(Qureg q) {
    return call_int("getNumAmps", Py_BuildValue("(O)", py_qureg(q)));
}

}  // extern "C"
