// TPU capability probe — the build's analogue of the reference's SGX
// capability tool (reference tools/sgx-capability/check-sgx-capability.c
// probes CPUID/MSR for enclave support; here we probe for an attached TPU
// accelerator and the pieces the framework's native path needs).
//
// Checks, in order:
//   1. PCI bus: any device with Google's vendor id (0x1ae0) — TPU chips
//      enumerate there on TPU VMs.
//   2. Accelerator device nodes: /dev/accel*, /dev/vfio/ (libtpu's access
//      paths).
//   3. libtpu.so loadable via dlopen (the XLA:TPU runtime).
//   4. libcrypto (OpenSSL 3) loadable — required by the native USIG
//      module (minbft_tpu/native).
//
// Exit status: 0 = TPU hardware reachable, 1 = no TPU (the tests' CPU
// backend still works), 2 = probe error.  Modeled on the reference tool's
// tri-state exit so tools/prerequisite-check.sh can branch on it — for a
// person to read.  No program path may take status 1 as permission to
// continue a chip run on the CPU: a run meant for the chip that finds
// none fails (chip_smoke.py, benchmark/run.py, `peer run`'s placement line).

#include <dirent.h>
#include <dlfcn.h>

#include <cstdio>
#include <cstring>
#include <fstream>
#include <string>

namespace {

bool scan_pci_for_vendor(const char *vendor_hex) {
  DIR *dir = opendir("/sys/bus/pci/devices");
  if (dir == nullptr) return false;
  bool found = false;
  for (dirent *e = readdir(dir); e != nullptr; e = readdir(dir)) {
    if (e->d_name[0] == '.') continue;
    std::string path = std::string("/sys/bus/pci/devices/") + e->d_name + "/vendor";
    std::ifstream fh(path);
    std::string vendor;
    if (fh >> vendor && vendor == vendor_hex) {
      found = true;
      break;
    }
  }
  closedir(dir);
  return found;
}

int count_glob_dev(const char *prefix) {
  DIR *dir = opendir("/dev");
  if (dir == nullptr) return -1;
  int n = 0;
  for (dirent *e = readdir(dir); e != nullptr; e = readdir(dir)) {
    if (std::strncmp(e->d_name, prefix, std::strlen(prefix)) == 0) ++n;
  }
  closedir(dir);
  return n;
}

bool dlopen_ok(const char *name) {
  void *h = dlopen(name, RTLD_LAZY | RTLD_LOCAL);
  if (h != nullptr) {
    dlclose(h);
    return true;
  }
  return false;
}

}  // namespace

int main() {
  const bool pci = scan_pci_for_vendor("0x1ae0");
  const int accel = count_glob_dev("accel");
  const int vfio = count_glob_dev("vfio");
  const bool libtpu = dlopen_ok("libtpu.so");
  const bool libcrypto = dlopen_ok("libcrypto.so.3") || dlopen_ok("libcrypto.so");

  std::printf("pci google vendor (0x1ae0): %s\n", pci ? "yes" : "no");
  std::printf("/dev/accel* nodes:          %d\n", accel < 0 ? 0 : accel);
  std::printf("/dev/vfio* nodes:           %d\n", vfio < 0 ? 0 : vfio);
  std::printf("libtpu.so loadable:         %s\n", libtpu ? "yes" : "no");
  std::printf("libcrypto loadable:         %s\n", libcrypto ? "yes" : "no");

  if (accel < 0 && vfio < 0) {
    std::fprintf(stderr, "probe error: /dev unreadable\n");
    return 2;
  }
  const bool tpu = pci || accel > 0 || libtpu;
  std::printf("verdict: %s\n",
              tpu ? "TPU reachable" : "no TPU (tests' CPU backend only; chip runs fail)");
  return tpu ? 0 : 1;
}
